"""Roofline share of the SSD: the least time of a round's SSD work (the
larger of its operations over peak FLOP/s and its bytes over HBM
bandwidth, ``chipbench/mamba2_counts.py``) over its device self time per
round, in percent."""
from chipbench import counts, split


def read(run):
    ms = split.per_round_ms(run["trace"], "device_scopes", "mamba2.ssd", run["rounds"])
    flops, nbytes = run.get("ssd_flops_per_round"), run.get("ssd_bytes_per_round")
    if not ms or flops is None or nbytes is None or not run["peaks"]:
        return None
    return 100.0 * counts.roofline_seconds(flops, nbytes, run["peaks"]) / (ms / 1000.0)
