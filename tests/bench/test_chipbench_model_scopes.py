"""The split of a traced window by the round's and the model's named scopes
(``chipbench/model_scopes.py``) and the LM cell's metric readers: on
hand-made events, and on the fleet block's trace recorded on a TPU v5e,
where the model's scopes must change nothing."""
import json
from pathlib import Path

import pytest

import _chipbench_tiny as tiny
from chipbench import model_scopes, registry, split
from chipbench.trace import reduce_events

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000  # ns
BLOCK = "jit(block_fn)/while/body/closed_call/cond/branch_0_fun/"
LOCAL = BLOCK + "pisco.local/vmap(jvp())/while/body/closed_call/"
BACK = BLOCK + "pisco.comm/vmap(transpose(jvp()))/while/body/closed_call/checkpoint/"


@pytest.mark.parametrize("op_name, scope", [
    (LOCAL + "mamba2.ssd/bzlhn,bzshn,bzhls,bzshp->bzlhp/dot_general", "mamba2.ssd"),
    (BACK + "mamba2.ssd/bzlhn,bzhpn,bzlh->bzlhp/transpose", "mamba2.ssd"),
    (LOCAL + "mamba2.in_proj/dot_general", "mamba2.in_proj"),
    (LOCAL + "mamba2.conv/mul", "mamba2.conv"),
    (BACK + "mamba2.out_proj/dot_general", "mamba2.out_proj"),
    (BLOCK + "pisco.local/vmap(jvp(lm.embed))/gather", "lm.embed"),
    (BLOCK + "pisco.comm/vmap(transpose(jvp(lm.head)))/dot_general", "lm.head"),
    (BLOCK + "pisco.comm/mix/scatter-add", "mix"),
    (BLOCK + "pisco.local/add", "pisco.local"),
    ("jit(block_fn)/while", "other"),
    (None, "other"),
])
def test_scope_is_the_innermost_of_the_round_and_the_model(op_name, scope):
    assert model_scopes.scope_in(op_name) == scope


def test_model_scopes_name_the_program_s_scopes():
    from repro.models import mamba2, transformer

    program = {mamba2.SCOPE_IN_PROJ, mamba2.SCOPE_CONV, mamba2.SCOPE_SSD,
               mamba2.SCOPE_OUT_PROJ, transformer.SCOPE_EMBED, transformer.SCOPE_HEAD}
    assert set(model_scopes.MODEL_SCOPES) == program
    assert model_scopes.SCOPES[:len(split.SCOPES)] == split.SCOPES


def _by_hand():
    host = [("bench.window", 0, 100 * MS), ("bench.sync", 10 * MS, 100 * MS)]
    block = {"while.1": "jit(block_fn)/while",
             "fusion.1": LOCAL + "mamba2.ssd/dot_general",
             "fusion.2": BACK + "mamba2.ssd/add",
             "fusion.3": LOCAL + "mamba2.in_proj/dot_general",
             "fusion.4": BLOCK + "pisco.comm/mix/gather",
             "fusion.5": BLOCK + "pisco.local/sub"}
    ops = {"/device:TPU:0": [
        ("fusion.1", 5 * MS, 8 * MS),      # another program's fusion.1
        ("while.1", 10 * MS, 95 * MS),     # holds the round's operations
        ("fusion.1", 12 * MS, 30 * MS),
        ("fusion.2", 30 * MS, 50 * MS),
        ("fusion.3", 50 * MS, 60 * MS),
        ("fusion.4", 60 * MS, 70 * MS),
        ("fusion.5", 70 * MS, 75 * MS),
        ("fusion.3", 75 * MS, 80 * MS)]}
    modules = {"/device:TPU:0": [("jit_gather", 5 * MS, 8 * MS),
                                 ("jit_block_fn", 10 * MS, 95 * MS)]}
    return ops, host, modules, {"jit_block_fn": block}


def test_device_scopes_by_hand():
    ops, host, modules, op_names = _by_hand()
    got = model_scopes.device_scopes(ops, host, modules, op_names)
    assert got == pytest.approx({"mamba2.ssd": 0.038, "mamba2.in_proj": 0.015, "mix": 0.010,
                                 "pisco.local": 0.005, "other": 0.003 + 0.017})
    assert sum(got.values()) == pytest.approx(reduce_events(ops, host)["busy_s"])
    no_window = [h for h in host if h[0] != "bench.window"]
    assert model_scopes.device_scopes(ops, no_window, modules, op_names) == {}


def test_recorded_fleet_trace_splits_as_before():
    """The fleet block holds no model scope: over the round's and the
    model's scopes its split is the one ``chipbench.split`` recorded."""
    expected = json.loads((DATA / "pisco_expected.json").read_text())
    got = model_scopes.reduce_xplane(DATA / "pisco_trace.xplane.pb", [0], expected["op_names"])
    want = expected["reduction"]
    assert {k: got[k] for k in ("window_s", "busy_s")} == pytest.approx(
        {k: want[k] for k in ("window_s", "busy_s")})
    assert got["device_scopes"] == pytest.approx(want["device_scopes"])


def _readers():
    cell = registry.load_cell(tiny.REPO, "train.mamba2-370m.ring2")
    return cell, {m["name"]: cell.module("metrics", m["name"]) for m in cell.per_layer}


def test_lm_readers_on_a_run_record():
    """100 rounds, the SSD's 2 s of device time are 20 ms per round; its
    least time per round is the larger of its FLOPs over the bf16 peak and
    its bytes over HBM bandwidth."""
    cell, readers = _readers()
    assert set(readers) == {"ssd_device_ms.lm", "ssd_roofline.lm", "mfu.lm", "idle_share.lm"}
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    run = {"rounds": 100, "peaks": peaks, "flops_per_round": 197e12 * 0.1,
           "bytes_per_round": 1.0, "ssd_flops_per_round": 197e12 * 0.004,
           "ssd_bytes_per_round": 819e9 * 0.005,
           "trace": {"window_s": 40.0, "busy_s": 30.0,
                     "device_scopes": {"mamba2.ssd": 2.0, "pisco.local": 20.0}}}
    got = {n: r.read(run) for n, r in readers.items()}
    assert got == pytest.approx({"ssd_device_ms.lm": 20.0, "ssd_roofline.lm": 25.0,
                                 "mfu.lm": 25.0, "idle_share.lm": 25.0})


def test_lm_readers_read_nothing_without_the_model_s_scopes():
    """A program whose model has no scopes, or an untraced run: the SSD's
    metrics are left out."""
    _, readers = _readers()
    run = {"rounds": 100, "peaks": {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0},
           "flops_per_round": 1.0, "bytes_per_round": 1.0, "ssd_flops_per_round": 1.0,
           "ssd_bytes_per_round": 1.0,
           "trace": {"window_s": 40.0, "busy_s": 30.0, "device_scopes": {"pisco.local": 3.0}}}
    assert readers["ssd_device_ms.lm"].read(run) is None
    assert readers["ssd_roofline.lm"].read(run) is None
    untraced = dict(run, trace=None)
    assert all(r.read(untraced) is None for r in readers.values())
