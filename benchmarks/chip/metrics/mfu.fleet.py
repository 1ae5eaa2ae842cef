"""Roofline share of the whole round, sparse gossip included: the least time
per round (bytes-bound: the state read and written once, the batches read
once) times the rounds, over the traced window, in percent."""
from chipbench import readers


def read(run):
    return readers.roofline_share(run)
