"""Host milliseconds per round in the sample phase: RoundSampler.sample_block
through core.driver.sample_block, and predraw_schedule."""
from chipbench import readers


def read(run):
    return readers.per_round_ms(run, "sample")
