"""Training cells whose traced window is split by the model's own scopes.

Everything but the trace and the ``stale`` fault is ``runners/train.py``'s,
loaded through the registry: ``build``, ``setup``, ``window``, the
reference's readings and the comparison.  A traced run also compiles the block once more after the
window, for its instructions' ``op_name``
(``capture_pisco_trace.block_op_names``), and adds ``device_scopes`` to
``run["trace"]``: device seconds by innermost scope, the PISCO round's and
the model's (``chipbench/model_scopes.py``), also printed to standard
error with the number of compiles inside the window.  Where the program has no
model scopes, their operations fall under the round's scopes and the
model's metrics read nothing.

The run also carries the SSD's operations and bytes per round, which the
family counts, for the SSD's roofline share.
"""
from __future__ import annotations

import dataclasses
import json
import shutil
import sys
import tempfile
import time

from chipbench import compare, device, model_scopes, registry
from chipbench.trace import Phases, find_xplane

train = registry.load_module([registry.HERE], "runners", "train")
FAULTS = train.FAULTS


def _stale_on_host(block_fn):
    """Fault: a block that returns the state it was given.  ``train.py``'s
    keeps a copy of the state on the device beside the donated block, which
    at a model's full width does not fit on the chip; this one keeps the
    copy on the host."""
    import jax

    def stale(state, *rest):
        kept = jax.device_get(state)
        new, metrics = block_fn(state, *rest)
        jax.block_until_ready(metrics)
        for leaf in jax.tree.leaves(new):
            leaf.delete()
        return jax.device_put(kept), metrics

    return stale


def build(cell, seed: int, *, dtype=None, fault=None):
    """``train.build``, with the ``stale`` fault's copy on the host."""
    if fault != "stale":
        return train.build(cell, seed, dtype=dtype, fault=fault)
    prog = train.build(cell, seed, dtype=dtype)
    return dataclasses.replace(prog, block_fn=_stale_on_host(prog.block_fn))


def numbers_for(cell, seed: int, *, dtype=None, fault=None) -> dict:
    """``train.numbers_for`` over this runner's ``build``."""
    prog = build(cell, seed, dtype=dtype, fault=fault)
    sess = train.setup(prog)
    train.free(sess)
    ref = train.reference_readings(prog)
    return {"numbers": compare.training_numbers(sess.readings, ref),
            "worst_leaf": compare.worst_leaves(sess.readings, ref),
            "loss": {"program": sess.readings["loss"], "reference": ref["loss"]}}


def op_names(prog, state) -> dict:
    """``{module: {instruction: op_name}}`` of the compiled block."""
    import capture_pisco_trace

    return capture_pisco_trace.block_op_names(prog, state)


def run(cell, ctx) -> dict:
    """One run: set-up, the window (traced or not), the check."""
    import jax

    from repro.obs.profile import track_compile_time

    prog = build(cell, ctx.seed, dtype=ctx.dtype, fault=ctx.fault)
    sess = train.setup(prog)
    jax.block_until_ready(sess.state)
    setup_s = time.perf_counter() - ctx.t_start
    phases = Phases()
    summary = None
    with track_compile_time() as compiled:
        if ctx.trace:
            tmp = tempfile.mkdtemp(prefix="chipbench-trace-")
            jax.profiler.start_trace(tmp)
            try:
                win = train.window(sess, ctx.seconds, phases)
            finally:
                jax.profiler.stop_trace()
        else:
            win = train.window(sess, ctx.seconds, phases)
    print(f"chipbench: window compiles {compiled.compiles}", file=sys.stderr)
    peak = device.memory_peak_bytes(ctx.devices) if ctx.on_chip else 0
    if ctx.trace:
        summary = model_scopes.reduce_xplane(find_xplane(tmp), range(len(ctx.devices)),
                                             op_names(prog, sess.state))
        shutil.rmtree(tmp, ignore_errors=True)
        if summary is not None:
            print("chipbench: device seconds by scope "
                  + json.dumps(summary["device_scopes"], sort_keys=True), file=sys.stderr)
    train.free(sess)
    t0 = time.perf_counter()
    numbers = compare.training_numbers(sess.readings, train.reference_readings(prog))
    fam, cfg, t = cell.family, cell.config, cell.traffic
    return {
        "setup_s": setup_s,
        "window_s": win["window_s"],
        "check_s": time.perf_counter() - t0,
        "rounds": win["rounds"],
        "attempted": win["rounds"],
        "failed": win["failed"],
        "phase_s": {k: phases.total(k) for k in phases.seconds},
        "flops_per_round": fam.flops_per_round(cfg, t),
        "bytes_per_round": fam.bytes_per_round(cfg, t),
        "ssd_flops_per_round": fam.ssd_flops_per_round(cfg, t),
        "ssd_bytes_per_round": fam.ssd_bytes_per_round(cfg, t),
        "peaks": ctx.peaks,
        "trace": summary,
        "memory_peak_bytes": peak,
        "numbers": numbers,
    }
