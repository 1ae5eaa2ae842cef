"""Set-up seconds: process start to the first timed step (imports, weights and
data from the seed, compiling or loading the cache, the checked block)."""


def read(run):
    return run["setup_s"]
