"""Edge-list gossip scaling benchmark (DESIGN.md §12).

Per-round wall time and peak mixing-state memory for the edge-list/CSR
gossip path across fleet sizes n ∈ {64, 1024, 4096, 10^4}.  The workload
is a tiny per-agent quadratic (loss = 0.5·mean((w − target)^2)) so the
numbers isolate the mixing substrate, not the model.  Its agreement with
``W @ x`` is pinned by ``tests/test_sparse.py``.

Memory is reported analytically (the simulation is single-host, so resident
set tells you little): the mixing state is the directed edge arrays (2m
weights + 2m int32 sender/receiver indices) plus the (n,) self-weight
vector, beside the n×n float32 W it never materializes.

    PYTHONPATH=src python -m benchmarks.fig_sparse
"""
from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from benchmarks.common import save_result
from repro.core import (
    PiscoConfig,
    make_sparse_topology,
    replicate_params,
    run_training,
    sparse_mixing,
)

FLEET_SIZES = (64, 1024, 4096, 10_000)


def _workload(n: int, d: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    targets = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))

    def loss_fn(params, batch):
        return 0.5 * jnp.mean((params["w"] - batch) ** 2)

    def sampler(k):
        return jnp.stack([targets, targets]), targets

    x0 = replicate_params({"w": jnp.zeros(d, jnp.float32)}, n)
    return loss_fn, sampler, x0


def _run(n: int, d: int, mixing, rounds: int, seed: int = 0):
    loss_fn, sampler, x0 = _workload(n, d, seed)
    cfg = PiscoConfig(n_agents=n, t_o=2, eta_l=0.1, eta_c=1.0, p=0.1, seed=seed)
    return run_training(
        "pisco", loss_fn, x0, cfg, mixing, sampler,
        rounds=rounds, driver="scan", block_size=rounds,
    )


def _mixing_state_bytes(n: int, m: int, sparse: bool) -> int:
    if sparse:
        # directed edge weights (2m f32) + senders/receivers (2m i32 each)
        # + self weights (n f32)
        return 2 * m * 4 + 2 * (2 * m * 4) + n * 4
    return n * n * 4  # the n×n float32 W, had it been materialized


def run(quick: bool = True) -> dict:
    d = 8 if quick else 256
    rounds = 4 if quick else 20
    results = {}
    for n in FLEET_SIZES:
        topo = make_sparse_topology("ring", n)
        mixing = sparse_mixing(topo)
        # warm-up run compiles the block; the timed run measures steady state
        _run(n, d, mixing, 1)
        t0 = time.perf_counter()
        hist = _run(n, d, mixing, rounds)
        dt = time.perf_counter() - t0
        m = topo.n_edges
        results[f"n={n}"] = {
            "n_agents": n,
            "n_edges": m,
            "rounds": rounds,
            "per_round_s": dt / rounds,
            "sparse_mixing_state_bytes": _mixing_state_bytes(n, m, True),
            "dense_mixing_state_bytes": _mixing_state_bytes(n, m, False),
            "final_loss": float(hist.loss[-1]),
        }

    payload = {"results": results, "quick": quick}
    save_result("BENCH_sparse", payload)
    return payload


def memory_ratio(results: dict) -> float:
    """n×n W / edge-list mixing-state memory ratio at the largest fleet."""
    biggest = max(results.values(), key=lambda r: r["n_agents"])
    return biggest["dense_mixing_state_bytes"] / max(
        1, biggest["sparse_mixing_state_bytes"]
    )


if __name__ == "__main__":
    payload = run()
    for k, r in payload["results"].items():
        print(
            f"{k}: {r['per_round_s'] * 1e3:.2f} ms/round, "
            f"mixing state {r['sparse_mixing_state_bytes']:,} B edge list vs "
            f"{r['dense_mixing_state_bytes']:,} B n×n W"
        )
