"""Training cells: the scan driver's timed path, its window, and its check.

The window drives what ``Experiment``'s scan driver drives:
``ExperimentSpec.make_mixing``, a ``RoundSampler`` over the family's data
through ``core.driver.sample_block``, ``predraw_schedule``, the algorithm
bound by ``get_algorithm(...).bind`` and the donated ``make_block_fn``
block.

Set-up builds one state from the seed and drives it through one block with
the traffic's ``check_flags``; the program's readings of that block are
kept, and the same state goes on into the window.  After the window the
state is freed and the reference follows the same rounds from the same
weights, on minibatches it gathers itself from the raw data by the
sampler's documented rule (``reference/federated.py``).
"""
from __future__ import annotations

import dataclasses
import gc
import shutil
import tempfile
import time
from functools import partial
from typing import Any, Optional

import numpy as np

from chipbench import compare, device
from chipbench.trace import Phases, find_xplane, reduce_xplane

FAULTS = ("stale", "halfbatch", "nomix", "swapped")


def key_of(seed: int):
    """A JAX key for any whole-number seed (``PRNGKey`` would wrap one that
    needs more than 32 bits)."""
    import jax

    return jax.random.PRNGKey(int(np.random.SeedSequence(int(seed)).generate_state(1)[0]))


def by_leaf(tree, convert=float) -> dict:
    import jax

    return {jax.tree_util.keystr(p): convert(v)
            for p, v in jax.tree_util.tree_leaves_with_path(tree)}


def readings_of(loss, g, dx) -> dict:
    """The losses of the checked rounds, and for G and for X - X^0 the
    (leaf norms, agents' row norms) pair that ``reference/pisco.norms``
    gives."""
    return {"loss": [float(v) for v in np.asarray(loss)],
            "g_norm": by_leaf(g[0]), "dx_norm": by_leaf(dx[0]),
            "g_rows": by_leaf(g[1], np.asarray), "dx_rows": by_leaf(dx[1], np.asarray)}


@dataclasses.dataclass
class Program:
    """The objects of the timed path for one cell and seed."""

    cell: Any
    seed: int
    dtype: str
    loss: Any
    bound: Any
    block_fn: Any
    sampler: Any
    init: Any  # jitted key -> parameters, the benchmark's weights

    @property
    def rounds_per_block(self) -> int:
        return int(self.cell.traffic["block_rounds"])


def _half_batch(loss):
    """Fault: half of each agent's batch left out, the mean over the rest."""
    import jax

    def half(params, batch):
        return loss(params, jax.tree.map(lambda a: a[: a.shape[0] // 2], batch))

    return half


def _cast_inputs(loss, dtype: str):
    """The program at a lower precision than its configuration: every
    floating input of a batch in ``dtype`` (the weights are made in it)."""
    import jax
    import jax.numpy as jnp

    def cast(params, batch):
        return loss(params, jax.tree.map(
            lambda a: a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating) else a, batch))

    return cast


def _stale(block_fn):
    """Fault: a block that returns the state it was given."""
    import jax
    import jax.numpy as jnp

    def stale(state, *rest):
        _, metrics = block_fn(jax.tree.map(jnp.copy, state), *rest)
        return state, metrics

    return stale


def build(cell, seed: int, *, dtype: Optional[str] = None, fault: Optional[str] = None) -> Program:
    import jax

    from repro.core.algorithms import get_algorithm
    from repro.core.driver import make_block_fn
    from repro.core.experiment import ExperimentSpec
    from repro.data.federated import RoundSampler

    t, cfg, fam = cell.traffic, cell.config, cell.family
    dtype = dtype or cfg["dtype"]
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault {fault!r} not in {FAULTS}")
    loss = fam.program_loss(cfg, dtype)
    if dtype != cfg["dtype"]:
        loss = _cast_inputs(loss, dtype)
    if fault == "halfbatch":
        loss = _half_batch(loss)
    spec = ExperimentSpec.create(
        algo=t["algo"], n_agents=t["agents"], t_o=t["t_o"], eta_l=t["eta_l"],
        eta_c=t["eta_c"], p=t["p"], seed=seed, topology=t["topology"], sparse=t["sparse"],
        driver="scan", block_size=t["block_rounds"])
    data = fam.dataset(cfg, t, seed, key_of(seed + 1))
    if fault == "swapped":  # agents 0 and 1 train on each other's shares
        swap = np.r_[1, 0, 2:t["agents"]]
        data = dataclasses.replace(data, x_train=data.x_train[swap], y_train=data.y_train[swap])
    sampler = RoundSampler(data, batch_size=t["batch"], t_o=t["t_o"], seed=seed)
    mixing = spec.make_mixing()
    if fault == "nomix":
        mixing = dataclasses.replace(mixing, gossip=lambda x: x, global_avg=lambda x: x)
    bound = get_algorithm(t["algo"]).bind(loss, spec.config, mixing)
    block_fn = make_block_fn(bound)
    if fault == "stale":
        block_fn = _stale(block_fn)
    init = jax.jit(partial(fam.init_params, cfg, dtype=dtype))
    return Program(cell, seed, dtype, loss, bound, block_fn, sampler, init)


@dataclasses.dataclass
class Session:
    program: Program
    state: Any
    readings: dict  # the program's readings of the checked block (``readings_of``)


def setup(prog: Program) -> Session:
    """Weights from the seed, the initial state, and the checked block."""
    import jax
    import jax.numpy as jnp

    from repro.core.driver import sample_block
    from repro.core.pisco import replicate_params

    cell, t = prog.cell, prog.cell.traffic
    key = key_of(prog.seed)
    params = prog.init(key)
    want = cell.family.program_shapes(cell.config, prog.dtype)
    got = jax.eval_shape(lambda: params)
    # the control's weights are in its own dtype; their shapes still match
    same = (lambda a, b: a.shape == b.shape) if prog.dtype != cell.config["dtype"] else (
        lambda a, b: (a.shape, a.dtype) == (b.shape, b.dtype))
    if jax.tree.structure(want) != jax.tree.structure(got) or not all(
            map(same, jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("the benchmark's weights do not match the program's layout")
    x0 = replicate_params(params, t["agents"])
    del params
    _, comm0 = prog.sampler(-1)
    state = prog.bound.init(prog.loss, x0, comm0)
    del x0, comm0
    r = prog.rounds_per_block
    flags = np.asarray(t["check_flags"], dtype=bool)
    if flags.shape != (r,):
        raise ValueError(f"check_flags must hold {r} rounds")
    local, comm = sample_block(prog.sampler, 0, r)
    state, metrics = prog.block_fn(state, jnp.asarray(flags), local, comm)
    del local, comm
    init_fn = partial(cell.family.init_params, cell.config, dtype=prog.dtype)
    f32 = lambda tree: jax.tree.map(lambda a: a.astype(jnp.float32), tree)
    dx = jax.jit(lambda x, k: jax.tree.map(lambda a, b: a - b[None], f32(x), f32(init_fn(k))))
    norms = cell.module("reference", t["algo"]).norms
    readings = readings_of(metrics.loss, norms(f32(state.g)), norms(dx(state.x, key)))
    return Session(prog, state, readings)


def window(sess: Session, seconds: float, phases: Phases) -> dict:
    """Blocks until ``seconds`` have passed; the window closes when the last
    block dispatched has finished, and every round of every block counts."""
    import jax
    import jax.numpy as jnp

    from repro.core.driver import predraw_schedule, sample_block

    prog = sess.program
    r = prog.rounds_per_block
    k, blocks, losses, pending = r, 0, [], None
    with phases("window"):
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while True:
            with phases("sample"):
                flags = predraw_schedule(prog.bound.schedule, k, k + r)
                local, comm = sample_block(prog.sampler, k, k + r)
            with phases("dispatch"):
                sess.state, metrics = prog.block_fn(sess.state, jnp.asarray(flags), local, comm)
            if pending is not None:
                with phases("sync"):
                    losses.append(np.asarray(pending))
            pending, k, blocks = metrics.loss, k + r, blocks + 1
            if time.perf_counter() >= deadline:
                break
        with phases("sync"):
            losses.append(np.asarray(pending))
            jax.block_until_ready(sess.state)
        t1 = time.perf_counter()
    losses = np.concatenate(losses)
    return {"rounds": blocks * r, "window_s": t1 - t0,
            "failed": int(np.sum(~np.isfinite(losses)))}


def reference_readings(prog: Program) -> dict:
    """The reference over the checked block's rounds, from the benchmark's
    weights and raw data in float32, on the minibatches it gathers itself."""
    import jax

    cell, t = prog.cell, prog.cell.traffic
    fam, model = cell.family, cell.reference
    algo = cell.module("reference", t["algo"])
    data = cell.module("reference", "federated")
    x, y = fam.raw_data(cell.config, t, key_of(prog.seed + 1))
    rows = data.split_rows(np.asarray(y), t["agents"], prog.seed)
    batches = data.make_batches(x, y, rows, prog.seed, t["t_o"], t["batch"])
    x0 = jax.jit(partial(fam.init_params, cell.config, dtype="float32"))(key_of(prog.seed))
    with jax.default_matmul_precision("highest"):
        out = algo.run(
            partial(model.loss, cfg=cell.config), x0, batches, list(t["check_flags"]),
            n_agents=t["agents"], t_o=t["t_o"], eta_l=t["eta_l"], eta_c=t["eta_c"],
            topology=t["topology"], agent_chunk=model.AGENT_CHUNK)
    return readings_of(out["loss"], out["g"], out["dx"])


def free(sess: Session) -> None:
    sess.state = None
    gc.collect()


def run(cell, ctx) -> dict:
    """One run: set-up, the window (traced or not), the check."""
    import jax

    prog = build(cell, ctx.seed, dtype=ctx.dtype, fault=ctx.fault)
    sess = setup(prog)
    jax.block_until_ready(sess.state)
    setup_s = time.perf_counter() - ctx.t_start
    phases = Phases()
    summary = None
    if ctx.trace:
        tmp = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(tmp)
        try:
            win = window(sess, ctx.seconds, phases)
        finally:
            jax.profiler.stop_trace()
        summary = reduce_xplane(find_xplane(tmp), range(len(ctx.devices)))
        shutil.rmtree(tmp, ignore_errors=True)
    else:
        win = window(sess, ctx.seconds, phases)
    peak = device.memory_peak_bytes(ctx.devices) if ctx.on_chip else 0
    free(sess)
    t0 = time.perf_counter()
    numbers = compare.training_numbers(sess.readings, reference_readings(prog))
    fam, t = cell.family, cell.traffic
    return {
        "setup_s": setup_s,
        "window_s": win["window_s"],
        "check_s": time.perf_counter() - t0,
        "rounds": win["rounds"],
        "attempted": win["rounds"],
        "failed": win["failed"],
        "phase_s": {k: phases.total(k) for k in phases.seconds},
        "flops_per_round": fam.flops_per_round(cell.config, t),
        "bytes_per_round": fam.bytes_per_round(cell.config, t),
        "peaks": ctx.peaks,
        "trace": summary,
        "memory_peak_bytes": peak,
        "numbers": numbers,
    }


def numbers_for(cell, seed: int, *, dtype: Optional[str] = None,
                fault: Optional[str] = None) -> dict:
    """The compared numbers of one seed without a window, set-up's checked
    block against the reference (what the limits are read from), with the
    losses of both and the leaves that set the norm gaps."""
    prog = build(cell, seed, dtype=dtype, fault=fault)
    sess = setup(prog)
    free(sess)
    ref = reference_readings(prog)
    return {"numbers": compare.training_numbers(sess.readings, ref),
            "worst_leaf": compare.worst_leaves(sess.readings, ref),
            "loss": {"program": sess.readings["loss"], "reference": ref["loss"]}}
