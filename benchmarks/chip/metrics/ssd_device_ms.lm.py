"""Device self time per round of the SSD, the operations whose innermost
named scope is ``mamba2.ssd`` (dt, A, the chunked scan and the D skip, in
the forward and the backward pass), in a traced window."""
from chipbench import split


def read(run):
    return split.per_round_ms(run["trace"], "device_scopes", "mamba2.ssd", run["rounds"])
