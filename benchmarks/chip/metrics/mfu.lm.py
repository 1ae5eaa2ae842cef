"""Roofline share of the whole round: the least time per round (Mamba-2's
training FLOPs of every agent's minibatches over the bf16 peak, or PISCO's
state and the token windows over HBM bandwidth, whichever is larger) times
the rounds, over the traced window, in percent."""
from chipbench import readers


def read(run):
    return readers.roofline_share(run)
