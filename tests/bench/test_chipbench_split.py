"""The split of a traced window by the program's own spans and scopes
(``chipbench/split.py``): on hand-made events, on the compiled fleet block,
and on a small trace of the fleet block recorded on a TPU v5e
(``benchmarks/chip/capture_pisco_trace.py``)."""
import contextlib
import io
import json
from pathlib import Path

import pytest

import _chipbench_tiny as tiny
from chipbench import registry
from chipbench.split import (
    SCOPES,
    hlo_op_names,
    per_round_ms,
    read_xplane,
    reduce_split,
    scope_of,
)
from chipbench.trace import reduce_events

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000  # ns
BLOCK = "jit(block_fn)/while/body/closed_call/cond/branch_0_fun/"


@pytest.mark.parametrize("op_name, scope", [
    (BLOCK + "pisco.local/while/body/closed_call/vmap(jvp())/dot_general", "pisco.local"),
    (BLOCK + "pisco.comm/mix/scatter-add", "mix"),
    (BLOCK + "pisco.comm/vmap(transpose(jvp()))/reduce_sum", "pisco.comm"),
    (BLOCK + "pisco.metrics/integer_pow", "pisco.metrics"),
    ("jit(block_fn)/while", "other"),
    (BLOCK + "add", "other"),
    # a fused instruction's own path comes first
    (BLOCK + "pisco.comm/add;cond/branch_1_fun/pisco.comm/mix/broadcast_in_dim", "pisco.comm"),
    ("jit(f)/vmap(pisco.local)/mul", "pisco.local"),
    (None, "other"),
])
def test_scope_is_the_innermost_of_the_four(op_name, scope):
    assert scope_of(op_name) == scope


def test_hlo_op_names_fall_back_to_the_fused_root():
    text = "\n".join([
        "HloModule jit_block_fn, is_scheduled=true",
        "",
        "%fused_computation.3 (param_0: f32[4]) -> f32[4] {",
        "  %param_0 = f32[4]{0} parameter(0)",
        '  ROOT %add.1 = f32[4]{0} add(%param_0, %param_0), metadata={op_name="jit(f)/mix/add"}',
        "}",
        "",
        "ENTRY %main.9 (p: f32[4]) -> f32[4] {",
        "  %p = f32[4]{0} parameter(0)",
        '  %fusion.7 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused_computation.3',
        '  ROOT %multiply_fusion = f32[4]{0} fusion(%fusion.7), kind=kLoop, '
        'calls=%fused_computation.3, metadata={op_name="jit(f)/pisco.comm/mul"}',
        "}",
    ])
    module, names = hlo_op_names(text)
    assert module == "jit_block_fn"
    assert names == {"add.1": "jit(f)/mix/add", "fusion.7": "jit(f)/mix/add",
                     "multiply_fusion": "jit(f)/pisco.comm/mul"}


def test_hlo_op_names_of_the_compiled_fleet_block(tmp_path):
    """The tiny fleet cell's block, built by the cell's runner: every
    instruction that runs has an op_name, and each of the four scopes holds
    some of them."""
    import jax
    import jax.numpy as jnp

    from repro.core.driver import sample_block
    from repro.core.pisco import replicate_params

    root = tiny.make_root(tmp_path)
    cell = registry.load_cell(root, "tiny.fleet")
    prog = cell.runner.build(cell, 11)
    t = cell.traffic
    _, comm0 = prog.sampler(-1)
    state = prog.bound.init(prog.loss, replicate_params(prog.init(jax.random.PRNGKey(0)),
                                                        t["agents"]), comm0)
    local, comm = sample_block(prog.sampler, 0, t["block_rounds"])
    flags = jnp.zeros((t["block_rounds"],), bool)
    module, names = hlo_op_names(prog.block_fn.lower(state, flags, local, comm).compile().as_text())
    assert module == "jit_block_fn"
    assert set(SCOPES) <= {scope_of(n) for n in names.values()}


def _by_hand():
    host = [("bench.window", 0, 100 * MS),
            ("bench.sample", 0, 40 * MS),
            ("repro.outer", 0, 40 * MS),
            ("repro.sample.gather", 2 * MS, 30 * MS),
            ("repro.sample.put", 30 * MS, 38 * MS),
            ("bench.dispatch", 40 * MS, 42 * MS),
            ("bench.sync", 42 * MS, 100 * MS),
            ("repro.late", 95 * MS, 120 * MS),     # clipped to the window
            ("repro.after", 150 * MS, 160 * MS)]   # outside it
    block = {"while.1": "jit(block_fn)/while",
             "fusion.1": BLOCK + "pisco.local/while/body/closed_call/sub",
             "fusion.2": BLOCK + "pisco.comm/mix/gather",
             "fusion.3": BLOCK + "pisco.comm/sub",
             "fusion.4": BLOCK + "pisco.metrics/reduce_sum"}
    ops = {"/device:TPU:0": [
        ("fusion.1", 10 * MS, 15 * MS),    # another program's fusion.1
        ("while.1", 20 * MS, 90 * MS),     # holds the round's operations
        ("fusion.1", 22 * MS, 40 * MS),
        ("fusion.2", 40 * MS, 50 * MS),
        ("fusion.3", 50 * MS, 60 * MS),
        ("fusion.4", 60 * MS, 65 * MS),
        ("fusion.2", 65 * MS, 70 * MS)]}
    modules = {"/device:TPU:0": [("jit_slice", 10 * MS, 15 * MS),
                                 ("jit_block_fn", 20 * MS, 90 * MS)]}
    return ops, host, modules, {"jit_block_fn": block}


def test_split_by_hand():
    ops, host, modules, op_names = _by_hand()
    s = reduce_split(ops, host, modules, op_names)
    assert s["program_spans"] == pytest.approx({
        "repro.outer": 0.040, "repro.sample.gather": 0.028, "repro.sample.put": 0.008,
        "repro.late": 0.005})
    # the while holds none of its body's time; the other program's
    # fusion.1 is not the block's
    assert s["device_scopes"] == pytest.approx({
        "pisco.local": 0.018, "mix": 0.015, "pisco.comm": 0.010, "pisco.metrics": 0.005,
        "other": 0.005 + 0.022})
    busy = reduce_events(ops, host)["busy_s"]
    assert sum(s["device_scopes"].values()) == pytest.approx(busy)
    # idle 0..10, 15..20 and 90..100, by the innermost span
    assert s["idle_spans"] == pytest.approx({
        "repro.outer": 0.002, "repro.sample.gather": 0.013, "repro.late": 0.005,
        "other": 0.005})


def test_split_needs_a_window_and_names_for_scopes():
    ops, host, modules, op_names = _by_hand()
    assert "device_scopes" not in reduce_split(ops, host, modules)
    assert reduce_split(ops, [h for h in host if h[0] != "bench.window"], modules) is None
    # without the program runs no operation is known to be the block's
    assert reduce_split(ops, host, None, op_names)["device_scopes"] == pytest.approx(
        {"other": 0.075})


def test_program_spans_leave_the_harness_reduction_as_it_was():
    ops, host, _, _ = _by_hand()
    bench_only = [h for h in host if not h[0].startswith("repro.")]
    assert reduce_events(ops, host) == reduce_events(ops, bench_only)


def test_per_round_ms_is_none_where_the_summary_holds_nothing():
    summary = {"program_spans": {"repro.sample.gather": 0.5}, "device_scopes": {}}
    assert per_round_ms(summary, "program_spans", "repro.sample.gather", 4) == 125.0
    assert per_round_ms(summary, "device_scopes", "pisco.local", 4) is None
    assert per_round_ms(summary, "idle_spans", "other", 4) is None
    assert per_round_ms(None, "program_spans", "repro.sample.gather", 4) is None
    assert per_round_ms(summary, "program_spans", "repro.sample.gather", 0) is None


def _recorded():
    expected = json.loads((DATA / "pisco_expected.json").read_text())
    ops, host, modules = read_xplane(DATA / "pisco_trace.xplane.pb")
    summary = reduce_events(ops, host)
    summary.update(reduce_split(ops, host, modules, expected["op_names"], top=None))
    return expected, ops, host, summary


def test_recorded_pisco_trace_scopes_hold_the_round():
    """The fleet block at 64 agents, two blocks in a window: each scope
    holds device time, the scopes with ``other`` sum to the busy time, and
    what ``other`` holds is not the round's own work: the sampler's slices
    (other programs), copies XLA inserted (no op_name), and the scan and
    cond around the round (an op_name outside every round function)."""
    expected, ops, host, s = _recorded()
    scopes = s["device_scopes"]
    assert all(scopes.get(k, 0.0) > 0 for k in SCOPES)
    assert sum(scopes.values()) == pytest.approx(s["busy_s"], rel=0.02)
    names = expected["op_names"]["jit_block_fn"]
    unscoped = {n for n, _ in s["other_ops"]}
    for module, _, op in (n.partition("/") for n in unscoped):
        assert module != "jit_block_fn" or "/cond/branch_" not in names.get(op, "")
    # at 64 agents the copies weigh more than at the cell's 4096, where the
    # scopes hold three quarters of busy time (PERF.md section 5)
    assert sum(scopes[k] for k in SCOPES) >= 0.5 * s["busy_s"]


def test_recorded_pisco_trace_sample_spans_sit_in_the_sample_phase():
    expected, _, host, s = _recorded()
    phases = [(a, b) for n, a, b in host if n == "bench.sample"]
    spans = [(n, a, b) for n, a, b in host if n.startswith("repro.sample.")]
    assert {n for n, _, _ in spans} == {"repro.sample.gather", "repro.sample.put"}
    assert all(any(pa <= a and b <= pb for pa, pb in phases) for _, a, b in spans)
    assert len(phases) == expected["rounds"] // 4  # one sample phase per 4-round block
    assert len(spans) == 2 * len(phases)
    program = s["program_spans"]
    sample = expected["phases"]["sample"]
    assert program["repro.sample.gather"] + program["repro.sample.put"] <= sample
    assert s["window_s"] == pytest.approx(expected["phases"]["window"], rel=1e-3)


def test_capture_script_rehearses_off_chip():
    """The capture script's whole path at 16 agents on the CPU: one block in
    the window, no compile in it, and no device trace to split."""
    import importlib.util

    path = tiny.BENCH / "capture_pisco_trace.py"
    spec = importlib.util.spec_from_file_location("capture_pisco_trace", path)
    script = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(script)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert script.main(["--seed", "4000000007", "--seconds", "0", "--agents", "16",
                            "--off-chip"]) == 0
    got = json.loads(out.getvalue().strip().splitlines()[-1])
    assert got["agents"] == 16 and got["rounds"] == 4
    assert got["window_compiles"] == 0
    assert got["host_sample_ms"] > 0
    assert got["repro.sample.gather_ms"] is None and got["mix_device_ms"] is None
