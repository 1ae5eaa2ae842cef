"""The LM cell at smoke size for the benchmark's CPU tests: mamba2-370m's
configuration with tiny widths (d_model 64, 2 layers, 4 heads, d_state 16,
chunk 16, vocabulary 256) and its traffic with 64-token windows."""
from __future__ import annotations

import json

import _chipbench_tiny as tiny

REAL = "train.mamba2-370m.ring2"
NAME = "tiny.lm"


def tiny_config(**changes) -> dict:
    cfg = json.loads((tiny.BENCH / "configs" / "mamba2-370m.json").read_text())
    for key in ("published", "deployment", "assumed"):
        cfg.pop(key)
    cfg.update(n_layers=2, d_model=64, d_inner=128, n_heads=4, head_dim=32, d_state=16,
               chunk=16, vocab_size=256)
    cfg.update(changes)
    return cfg


def tiny_traffic(**changes) -> dict:
    return tiny.traffic_like("ring2", seq=64, samples_per_agent=8, **changes)


CELLS = {NAME: ("tiny-mamba2", tiny_config(), "tiny-ring2", tiny_traffic(), REAL)}


def make_root(tmp):
    return tiny.make_root(tmp, CELLS)
