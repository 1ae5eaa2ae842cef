"""Observability layer conformance (DESIGN.md §16).

Pins the contracts of ``repro.obs``:

* the :class:`~repro.obs.trace.TraceRecorder` span model — round spans with
  byte/sim-second attribution, phase children that partition each round,
  per-agent event spans, serve request lifecycles — and its Chrome-trace
  export, schema-validated exactly as ui.perfetto.dev would parse it;
* telemetry is free when off: a run with a recorder attached produces
  bitwise-identical ``History`` losses to a run without one, and all seven
  protocols × {loop, scan, events} drivers attribute identical bytes and
  simulated seconds to every round span (pisco in the fast lane, the other
  six in the full lane);
* the profiler's spans (``obs.trace.span``, the sampler's gather and put)
  and the PISCO round's named scopes, which change only the HLO's
  ``op_name`` metadata;
* the metrics registry (counters monotone, histograms quantile-correct,
  JSONL sink round-trips) and the ``History`` / ``ServeReport`` exporters;
* the perf-regression gate: tolerance kinds, missing-metric semantics,
  manifest-driven artifact pairing, and the end-to-end CLI — which must
  pass a baseline against itself and fail an injected 2× slowdown.
"""
import contextlib
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from conftest import make_logreg_problem
from repro.core import Experiment, ExperimentSpec, registered_algorithms
from repro.core.compression import make_byte_model
from repro.core.trainer import History
from repro.obs import (
    GATES,
    SPAN_PREFIX,
    MetricGate,
    MetricsRegistry,
    TraceRecorder,
    bench_key,
    compare_dirs,
    compare_payloads,
    profile_capture,
    read_jsonl,
    span,
    to_chrome_trace,
    track_compile_time,
    validate_chrome_trace,
    write_trace,
)
from repro.serve.batcher import Request
from repro.serve.load import ServeReport
from repro.sim.costmodel import make_time_model

N_AGENTS = 5
ROUNDS = 10


def _pieces(n=N_AGENTS, with_eval=False):
    loss_fn, full_grad_sq, sampler_factory, d = make_logreg_problem(n_agents=n)
    out = dict(
        loss_fn=loss_fn,
        params0={"w": jnp.zeros(d)},
        sampler_factory=lambda s: sampler_factory(s.config.t_o),
    )
    if with_eval:
        out["eval_fn"] = lambda p: {"grad_sq": full_grad_sq(p)}
    return out


def _spec(driver, **kw):
    base = dict(
        algo="pisco", n_agents=N_AGENTS, t_o=2, eta_l=0.1, p=0.2, seed=0,
        rounds=ROUNDS, driver=driver, systems="uniform",
    )
    if driver == "events":
        base["async_"] = "constant:buffer=3"
    base.update(kw)
    return ExperimentSpec.create(**base)


@pytest.fixture(scope="module")
def traced_runs():
    """One pisco run per driver with a recorder attached, plus a scan run
    without one (the recording-is-free twin).  Shared across tests — each
    run is seconds of jit; don't re-run per assertion."""
    plain = Experiment(_spec("scan"), **_pieces(with_eval=True)).run()
    hists, recs = {}, {}
    for driver in ("loop", "scan", "events"):
        rec = TraceRecorder(meta={"driver": driver})
        hists[driver] = Experiment(
            _spec(driver), recorder=rec, **_pieces(with_eval=True)
        ).run()
        recs[driver] = rec
    return plain, hists, recs


# ---------------------------------------------------------------------------
# TraceRecorder span model (pure python, no jax)
# ---------------------------------------------------------------------------


def test_recorder_round_spans_advance_the_clock():
    rec = TraceRecorder(meta={"kind": "unit"})
    rec.record_round(0, True, 100, parts={"local_steps": 0.25, "server_sync": 0.75})
    rec.record_round(1, False, 200, seconds=0.5)
    assert rec.clock_s == pytest.approx(1.5)
    table = rec.round_table()
    assert [(r, k, b) for r, k, b, _ in table] == [
        (0, "server_round", 100), (1, "gossip_round", 200)
    ]
    assert table[0][3] == pytest.approx(1.0)  # parts sum = span duration
    # phase children partition the round span, in execution order
    phases = [s for s in rec.spans if s.cat == "phase"]
    assert [p.name for p in phases] == ["local_steps", "server_sync"]
    assert phases[0].t0 == pytest.approx(0.0)
    assert phases[1].t0 == pytest.approx(0.25)


def test_recorder_clamps_negative_durations():
    rec = TraceRecorder()
    rec.add_span("host", "oops", 1.0, -0.5)
    assert rec.spans[-1].dur == 0.0


def test_recorder_serve_request_lifecycle():
    req = Request(
        rid=7, agent_id=3, prompt=np.zeros(4, np.int32), max_new_tokens=4,
        arrival_s=1.0, admit_s=1.5, first_token_s=2.0, done_s=3.0,
        prefill_s=0.5, decode_s=1.0, tokens=[1, 2, 3, 4], slot=2,
    )
    rec = TraceRecorder()
    rec.record_request(req)
    spans = [s for s in rec.spans if s.cat == "serve"]
    assert [s.name for s in spans] == ["queue", "prefill", "decode"]
    assert all(s.track == "agent 3" for s in spans)
    assert spans[0].t0 == pytest.approx(1.0)  # queue starts at arrival
    assert spans[0].dur == pytest.approx(0.5)
    assert spans[2].args["tokens"] == 4
    assert all(s.args["slot"] == 2 for s in spans)


# ---------------------------------------------------------------------------
# Chrome-trace export + schema validation
# ---------------------------------------------------------------------------


def test_chrome_export_schema_and_track_order(tmp_path):
    rec = TraceRecorder(meta={"kind": "unit"})
    rec.record_round(0, False, 64, seconds=0.25)
    rec.record_agent_round(0, 1, 0.0, 0.25, False, staleness=0)
    rec.record_agent_round(0, 0, 0.0, 0.25, False, staleness=0)
    rec.add_instant("rounds", "eval", 0.25, grad_sq=0.5)
    rec.add_span("host", "compile", 0.0, 1e-3, cat="host")
    obj = write_trace(str(tmp_path / "t.json"), rec)
    validate_chrome_trace(obj)
    reloaded = json.load(open(tmp_path / "t.json"))
    assert reloaded == obj
    assert obj["otherData"]["kind"] == "unit"
    # track metadata orders rounds first, then host, then agents by index
    meta = [e for e in obj["traceEvents"] if e["ph"] == "M"
            and e["name"] == "thread_name"]
    order = [e["args"]["name"] for e in sorted(
        meta, key=lambda e: e["tid"])]
    assert order == ["rounds", "host", "agent 0", "agent 1"]
    # ts/dur are microseconds
    rnd = next(e for e in obj["traceEvents"]
               if e["ph"] == "X" and e["name"] == "gossip_round")
    assert rnd["dur"] == pytest.approx(0.25e6)


def test_validate_rejects_malformed_traces():
    rec = TraceRecorder()
    rec.record_round(0, True, 1)
    good = to_chrome_trace(rec)
    with pytest.raises(AssertionError):
        validate_chrome_trace([])  # array flavour not accepted
    with pytest.raises(AssertionError):
        validate_chrome_trace({"traceEvents": []})  # empty
    bad = json.loads(json.dumps(good))
    for e in bad["traceEvents"]:
        if e["ph"] == "X":
            e["dur"] = -1.0
    with pytest.raises(AssertionError):
        validate_chrome_trace(bad)
    bad2 = json.loads(json.dumps(good))
    bad2["traceEvents"] = [e for e in bad2["traceEvents"] if e["ph"] != "M"]
    with pytest.raises(AssertionError):  # spans on a track with no name
        validate_chrome_trace(bad2)


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------


def test_metrics_registry_counters_gauges_histograms():
    reg = MetricsRegistry(meta={"kind": "unit"})
    reg.counter("c").inc()
    reg.counter("c").inc(2.5)  # get-or-create returns the same instance
    assert reg.counter("c").value == pytest.approx(3.5)
    with pytest.raises(ValueError):
        reg.counter("c").inc(-1)
    reg.gauge("g").set(1.0)
    reg.gauge("g").set(-2.0)
    assert reg.gauge("g").value == -2.0
    reg.histogram("h").observe_many([3.0, 1.0, 2.0])
    snap = reg.snapshot()
    h = snap["metrics"]["h"]
    assert h["count"] == 3 and h["min"] == 1.0 and h["max"] == 3.0
    assert h["p50"] == pytest.approx(2.0)
    with pytest.raises(TypeError):  # name already bound to another type
        reg.gauge("c")
    assert reg.names() == ["c", "g", "h"]


def test_metrics_jsonl_sink_round_trips(tmp_path):
    path = tmp_path / "m.jsonl"
    for i in range(2):
        reg = MetricsRegistry(meta={"run": i})
        reg.counter("n").inc(i)
        reg.write_jsonl(str(path), extra_field=i * 10)
    lines = read_jsonl(str(path))
    assert len(lines) == 2
    assert lines[1]["meta"]["run"] == 1
    assert lines[1]["metrics"]["n"]["value"] == 1
    assert lines[1]["meta"]["extra_field"] == 10


# ---------------------------------------------------------------------------
# Cost-model phase decomposition
# ---------------------------------------------------------------------------


def test_round_parts_sum_to_round_time_exactly():
    from repro.core import replicate_params

    spec = _spec("scan", network="matching", participation=0.6)
    mixing = spec.make_mixing()
    x0 = replicate_params({"w": jnp.zeros(8)}, spec.config.n_agents)
    bm = make_byte_model(mixing, x0, spec.config.n_agents)
    tm = make_time_model(spec, bm, network=mixing.network)
    for k in range(6):
        for is_global in (False, True):
            parts = tm.round_parts(k, is_global)
            assert set(parts) == (
                {"local_steps", "server_sync"} if is_global
                else {"local_steps", "gossip_mix"}
            )
            # exact: both sides are the same two float adds
            assert sum(parts.values()) == tm.round_time(k, is_global)


# ---------------------------------------------------------------------------
# Recording is free; span attribution is driver-invariant
# ---------------------------------------------------------------------------


def test_recording_off_on_losses_bitwise_identical(traced_runs):
    plain, hists, _ = traced_runs
    np.testing.assert_array_equal(plain.loss, hists["scan"].loss)
    assert plain.is_global == hists["scan"].is_global
    assert plain.to_dict()["sim_time_s"] == hists["scan"].to_dict()["sim_time_s"]


def test_round_span_attribution_matches_across_drivers(traced_runs):
    _, _, recs = traced_runs
    tables = {d: r.round_table() for d, r in recs.items()}
    ref = tables["scan"]
    assert len(ref) == ROUNDS
    for table in tables.values():
        # kind and byte attribution exact; seconds allclose (the events
        # engine derives durations from availability-frontier differences,
        # which carry ~1e-16 float noise)
        assert [(r, k, b) for r, k, b, _ in table] == [
            (r, k, b) for r, k, b, _ in ref
        ]
        np.testing.assert_allclose(
            [t[3] for t in table], [t[3] for t in ref], rtol=1e-9
        )


@pytest.mark.parametrize(
    "algo",
    [
        # pisco gates the fast lane; the other six protocols (~10 s each for
        # the three-driver sweep) run in the full tier1-hypothesis lane
        a if a == "pisco" else pytest.param(a, marks=pytest.mark.slow)
        for a in registered_algorithms()
    ],
)
def test_span_parity_all_protocols(algo):
    rounds, n = 6, 4
    tables = {}
    for driver in ("loop", "scan", "events"):
        rec = TraceRecorder()
        kw = dict(algo=algo, rounds=rounds, n_agents=n)
        if driver == "events":
            kw["async_"] = "constant:buffer=2"
        Experiment(_spec(driver, **kw), recorder=rec, **_pieces(n=n)).run()
        tables[driver] = rec.round_table()
    ref = tables["scan"]
    assert len(ref) == rounds
    for table in tables.values():
        assert [(r, k, b) for r, k, b, _ in table] == [
            (r, k, b) for r, k, b, _ in ref
        ]
        np.testing.assert_allclose(
            [t[3] for t in table], [t[3] for t in ref], rtol=1e-9
        )


def test_scan_trace_has_phase_children_and_eval_instants(traced_runs):
    _, _, recs = traced_runs
    rec = recs["scan"]
    rounds = [s for s in rec.spans if s.cat == "round"]
    phases = [s for s in rec.spans if s.cat == "phase"]
    assert rounds and phases
    for rs in rounds:
        kids = [p for p in phases
                if rs.t0 - 1e-12 <= p.t0
                and p.t0 + p.dur <= rs.t0 + rs.dur + 1e-9]
        assert sum(p.dur for p in kids) == pytest.approx(rs.dur, abs=1e-12)
    evals = [i for i in rec.instants if i.name == "eval"]
    assert evals and all("grad_sq" in i.args for i in evals)


def test_events_trace_has_per_agent_tracks(traced_runs):
    _, _, recs = traced_runs
    rec = recs["events"]
    agent_tracks = [t for t in rec.tracks() if t.startswith("agent ")]
    assert len(agent_tracks) == N_AGENTS
    agent_spans = [s for s in rec.spans if s.cat == "agent"]
    assert len(agent_spans) == ROUNDS * N_AGENTS
    assert all("staleness" in s.args and "participant" in s.args
               for s in agent_spans)


def test_real_run_chrome_traces_validate(traced_runs, tmp_path):
    _, _, recs = traced_runs
    for driver, rec in recs.items():
        obj = write_trace(str(tmp_path / f"{driver}.json"), rec)
        validate_chrome_trace(obj)


# ---------------------------------------------------------------------------
# History export: sim-second split, round trip, telemetry
# ---------------------------------------------------------------------------


def test_history_sim_split_and_round_trip(traced_runs):
    plain, _, _ = traced_runs
    d = plain.to_dict()
    assert len(d["sim_time_a2a_s"]) + len(d["sim_time_a2s_s"]) == ROUNDS
    assert sum(d["sim_time_a2a_s"]) == pytest.approx(d["sim_time_a2a_total_s"])
    assert sum(d["sim_time_a2s_s"]) == pytest.approx(d["sim_time_a2s_total_s"])
    assert d["sim_time_a2a_total_s"] + d["sim_time_a2s_total_s"] == (
        pytest.approx(sum(d["sim_time_s"]))
    )
    # JSON-faithful round trip: rebuild and re-export
    h2 = History.from_dict(json.loads(json.dumps(d)))
    assert h2.to_dict() == d


def test_history_telemetry_registry(traced_runs):
    plain, _, _ = traced_runs
    snap = plain.telemetry(meta={"algo": "pisco"}).snapshot()
    m = snap["metrics"]
    assert m["train.rounds_gossip"]["value"] + m["train.rounds_server"][
        "value"] == ROUNDS
    assert m["train.round_bytes"]["count"] == ROUNDS
    assert m["train.bytes_a2a"]["value"] == plain.accountant.agent_to_agent_bytes
    assert snap["meta"]["algo"] == "pisco"


def test_serve_report_telemetry():
    reqs = [
        Request(rid=i, agent_id=i % 2, prompt=np.zeros(2, np.int32),
                max_new_tokens=2, arrival_s=float(i), admit_s=i + 0.5,
                done_s=i + 1.0, prefill_s=0.2, decode_s=0.3,
                tokens=[1, 2], slot=i % 3)
        for i in range(6)
    ]
    report = ServeReport(requests=reqs, clock_s=7.0)
    snap = report.telemetry(meta={"kind": "serve"}).snapshot()
    m = snap["metrics"]
    assert m["serve.requests"]["value"] == 6
    assert m["serve.tokens"]["value"] == 12
    assert m["serve.queue_wait_s"]["count"] == 6
    assert m["serve.slot.0.requests"]["value"] == 2


# ---------------------------------------------------------------------------
# Profiler hooks
# ---------------------------------------------------------------------------


def test_track_compile_time_sees_a_fresh_jit():
    @jax.jit
    def f(x):
        return x * 2.0 + 1.0

    with track_compile_time() as stats:
        f(jnp.arange(3.0)).block_until_ready()
    if stats.supported:
        assert stats.seconds >= 0.0
        assert any("compile" in k for k in stats.events)


def test_profile_capture_noop_and_real(tmp_path):
    with profile_capture(None):
        pass  # no-op must not touch the filesystem
    out = tmp_path / "prof"
    with profile_capture(str(out)):
        jnp.arange(4.0).sum().block_until_ready()
    assert any(out.rglob("*.xplane.pb"))


def test_profile_capture_that_cannot_start_raises(tmp_path):
    # one profiler session per process: the nested capture cannot start
    with profile_capture(str(tmp_path / "outer")):
        with pytest.raises(RuntimeError):
            with profile_capture(str(tmp_path / "inner")):
                pass


def test_track_compile_time_counts_compiles():
    @jax.jit
    def f(x):
        return x * 3.0 - 1.0

    x = jnp.arange(5.0)
    with track_compile_time() as first:
        f(x).block_until_ready()
    with track_compile_time() as again:
        f(x).block_until_ready()
    if first.supported:
        assert first.compiles == 1 and first.seconds > 0.0
        assert again.compiles == 0 and again.seconds == 0.0


# ---------------------------------------------------------------------------
# Profiler spans and the round's named scopes
# ---------------------------------------------------------------------------

ROUND_SCOPES = ("pisco.local", "pisco.comm", "mix", "pisco.metrics")


def _host_events(trace_dir, prefix=SPAN_PREFIX):
    """``(name, start, end, stats)`` of the host events named ``prefix...``
    in the one capture under ``trace_dir``."""
    from jax.profiler import ProfileData

    (path,) = Path(trace_dir).glob("plugins/profile/*/*.xplane.pb")
    data = ProfileData.from_file(str(path))
    return sorted(
        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
        for plane in data.planes if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events if ev.name.startswith(prefix)
    )


def test_span_is_a_noop_outside_a_capture(tmp_path):
    with span("before", rounds=1, bytes=8) as got:
        assert got is None
    with pytest.raises(ValueError):
        with span("raises"):
            raise ValueError("propagates")
    # nothing was kept for a later capture to write
    with profile_capture(str(tmp_path)):
        jnp.arange(3.0).sum().block_until_ready()
    assert _host_events(tmp_path) == []


def test_span_nests_and_carries_its_counts(tmp_path):
    with profile_capture(str(tmp_path)):
        with span("outer", rounds=4, bytes=1 << 33):
            with span("inner"):
                jnp.arange(3.0).sum().block_until_ready()
    got = {name: (s, e, stats) for name, s, e, stats in _host_events(tmp_path)}
    assert set(got) == {"repro.outer", "repro.inner"}
    (o0, o1, outer), (i0, i1, inner) = got["repro.outer"], got["repro.inner"]
    assert o0 <= i0 <= i1 <= o1
    assert outer == {"rounds": 4, "bytes": 1 << 33} and inner == {}


@pytest.mark.parametrize("block", [True, False], ids=["sample_block", "per_round"])
def test_round_sampler_spans_the_gather_and_the_put(tmp_path, block):
    from repro.data.federated import FederatedDataset, RoundSampler

    rng = np.random.default_rng(0)
    data = FederatedDataset.from_arrays(
        rng.random((96, 12), np.float32), rng.integers(0, 3, 96).astype(np.int32), 4,
        test_fraction=0.0)
    sampler = RoundSampler(data, batch_size=2, t_o=2, seed=1)
    with profile_capture(str(tmp_path)):
        local, comm = sampler.sample_block(3, 6) if block else sampler(3)
    events = sorted(_host_events(tmp_path), key=lambda ev: ev[1])
    # the indices go to the device first, then the gather runs there
    assert [ev[0] for ev in events] == ["repro.sample.put", "repro.sample.gather"]
    rounds = 3 if block else 1
    n_idx = rounds * 3 * data.n_agents * 2  # rounds x (t_o + 1) x agents x batch
    nbytes = sum(a.nbytes for a in (*local, *comm))
    assert events[0][3] == {"rounds": rounds, "bytes": 4 * n_idx}
    assert events[1][3] == {"rounds": rounds, "bytes": nbytes, "on": "device"}
    assert events[0][2] <= events[1][1]  # the gather starts after the put ends


def _fleet_block_hlo(local_opt=None, agents=16, rounds=4, batch=4):
    """Optimized HLO of the fleet's scan block: PISCO on a sparse ring,
    the paper's 784-32-10 MLP, at ``agents``."""
    from repro.core.algorithms import get_algorithm
    from repro.core.driver import make_block_fn
    from repro.core.pisco import replicate_params
    from repro.models.simple import mlp_init, mlp_loss

    spec = ExperimentSpec.create(
        algo="pisco", n_agents=agents, t_o=2, eta_l=0.5, eta_c=1.0, p=0.1, seed=0,
        topology="ring", driver="scan", block_size=rounds)
    bound = get_algorithm("pisco").bind(
        mlp_loss, spec.config, spec.make_mixing(), local_opt=local_opt)
    sds = jax.ShapeDtypeStruct

    def batches(*lead):
        return (sds((*lead, agents, batch, 784), jnp.float32),
                sds((*lead, agents, batch), jnp.int32))

    x0 = jax.eval_shape(
        lambda: replicate_params(mlp_init(jax.random.PRNGKey(0)), agents))
    state = jax.eval_shape(lambda x, c: bound.init(mlp_loss, x, c), x0, batches())
    return make_block_fn(bound).lower(
        state, sds((rounds,), jnp.bool_), batches(rounds, 2), batches(rounds)
    ).compile().as_text()


def _scope_paths(hlo: str):
    return [n.split(";")[0].split("/") for n in re.findall(r'op_name="([^"]*)"', hlo)]


@pytest.mark.parametrize("local_opt", [None, "momentum"], ids=["legacy", "rule"])
def test_fleet_block_carries_the_round_scopes(local_opt):
    paths = _scope_paths(_fleet_block_hlo(local_opt))
    assert set(ROUND_SCOPES) <= {part for path in paths for part in path}
    # each mix sits inside the communication step; nothing nests in a mix
    for path in paths:
        if "mix" in path:
            assert path[path.index("mix") - 1] == "pisco.comm"


def test_round_scopes_change_only_metadata(monkeypatch):
    scoped = _fleet_block_hlo()
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    plain = _fleet_block_hlo()
    assert not {part for path in _scope_paths(plain) for part in path} & set(ROUND_SCOPES)

    def strip(hlo):
        """The HLO less its metadata: each instruction's ``metadata={...}``
        and the tables of source files and stack frames it points into."""
        hlo = re.sub(r", metadata=\{[^}]*\}", "", hlo)
        tables = re.compile(r"^(FileNames|FunctionNames|FileLocations|StackFrames|\d+ .*)$")
        return "\n".join(line for line in hlo.splitlines() if not tables.match(line))

    assert strip(scoped) == strip(plain)
    assert " fusion(" in strip(plain) and " while(" in strip(plain)


# ---------------------------------------------------------------------------
# Perf-regression gate
# ---------------------------------------------------------------------------


def test_gate_kinds():
    ok = lambda fs: not any(f.failed for f in fs)
    base = {"t": 1.0, "h": 10.0, "m": 5.0, "f": True, "c": 2}
    gates = [
        MetricGate("t", "time", 2.0),
        MetricGate("h", "higher", 2.0),
        MetricGate("m", "match", 0.1),
        MetricGate("f", "flag"),
        MetricGate("c", "count", 1),
    ]
    assert ok(compare_payloads("x", base, dict(base), gates=gates))
    assert ok(compare_payloads(
        "x", base, {"t": 1.9, "h": 5.5, "m": 5.4, "f": True, "c": 3},
        gates=gates))
    for bad in (
        {**base, "t": 2.5}, {**base, "h": 4.0}, {**base, "m": 6.0},
        {**base, "f": False}, {**base, "c": 4},
    ):
        assert not ok(compare_payloads("x", base, bad, gates=gates))


def test_gate_missing_metric_semantics():
    gates = [MetricGate("a.b", "time", 2.0)]
    # absent from both → skipped (schema drift in an old baseline)
    (f,) = compare_payloads("x", {}, {}, gates=gates)
    assert f.status == "skipped" and not f.failed
    # absent only from baseline → skipped (new metric, no reference yet)
    (f,) = compare_payloads("x", {}, {"a": {"b": 1.0}}, gates=gates)
    assert f.status == "skipped"
    # absent only from fresh → failure (a gated metric disappeared)
    (f,) = compare_payloads("x", {"a": {"b": 1.0}}, {}, gates=gates)
    assert f.status == "missing" and f.failed


def test_gate_paths_resolve_in_committed_baselines():
    """Every registered gate path must exist in the committed artifacts —
    a renamed payload key would silently turn a gate into a skip."""
    from repro.obs.regress import load_artifacts, lookup

    art = os.path.join(os.path.dirname(__file__), "..", "artifacts", "bench")
    payloads = load_artifacts(art)
    assert set(GATES) <= set(payloads), "baseline artifact missing"
    for bench, gates in GATES.items():
        for gate in gates:
            found, _ = lookup(payloads[bench], gate.path)
            assert found, f"{bench}: gate path {gate.path} absent from baseline"


def _write_fixture_dirs(tmp_path, slowdown=1.0):
    base = tmp_path / "base"
    fresh = tmp_path / "fresh"
    base.mkdir(exist_ok=True)
    fresh.mkdir(exist_ok=True)
    payload = {
        "profiles": {
            "lognormal-stragglers": {
                "sync": {"total_sim_time_s": 10.0},
                "async": {"total_sim_time_s": 4.0},
            },
            "wan-gossip": {"async": {"total_sim_time_s": 20.0}},
            "free": {"bit_identical_loss": True},
        },
        "reprice": {"self_exact": True},
    }
    (base / "BENCH_async.json").write_text(json.dumps(payload))
    fresh_payload = json.loads(json.dumps(payload))
    for prof in fresh_payload["profiles"].values():
        for mode in ("sync", "async"):
            if mode in prof:
                prof[mode]["total_sim_time_s"] *= slowdown
    (fresh / "BENCH_async.json").write_text(json.dumps(fresh_payload))
    return base, fresh


def test_compare_dirs_passes_identical_and_fails_2x_slowdown(tmp_path):
    base, fresh = _write_fixture_dirs(tmp_path, slowdown=1.0)
    findings = compare_dirs(str(base), str(fresh))
    assert findings and not any(f.failed for f in findings)
    base, fresh = _write_fixture_dirs(tmp_path, slowdown=2.0)
    findings = compare_dirs(str(base), str(fresh))
    regressed = [f for f in findings if f.failed]
    assert len(regressed) == 3  # the three sim-time gates; flags still pass


def test_compare_dirs_follows_manifest_paths(tmp_path):
    base, fresh = _write_fixture_dirs(tmp_path)
    # rename the fresh artifact so only the manifest knows where it lives —
    # the gate must pair via the manifest index, not a filename convention
    (fresh / "BENCH_async.json").rename(fresh / "async.v2.json")
    (fresh / "MANIFEST.json").write_text(json.dumps({
        "schema_version": 1,
        "benches": {"async": {"path": "async.v2.json"}},
    }))
    findings = compare_dirs(str(base), str(fresh))
    assert findings and not any(f.failed for f in findings)


def test_check_regress_cli_exit_codes(tmp_path):
    from benchmarks.check_regress import main as gate_main

    base, fresh = _write_fixture_dirs(tmp_path, slowdown=1.0)
    assert gate_main(["--baseline", str(base), "--fresh", str(fresh)]) == 0
    base, fresh = _write_fixture_dirs(tmp_path, slowdown=2.0)
    assert gate_main(["--baseline", str(base), "--fresh", str(fresh)]) == 1
    # escape hatch: copy fresh over baseline, then the gate passes again
    assert gate_main([
        "--baseline", str(base), "--fresh", str(fresh), "--update-baselines",
    ]) == 0
    assert gate_main(["--baseline", str(base), "--fresh", str(fresh)]) == 0
    # an empty fresh dir is an error, not a silent pass
    empty = tmp_path / "empty"
    empty.mkdir()
    assert gate_main(["--baseline", str(base), "--fresh", str(empty)]) == 1


def test_write_manifest_indexes_bench_artifacts(tmp_path):
    from benchmarks.common import write_manifest

    (tmp_path / "BENCH_driver.json").write_text("{}")
    (tmp_path / "BENCH_async.json").write_text("{}")
    (tmp_path / "notes.json").write_text("{}")  # not a bench artifact
    path = write_manifest(str(tmp_path))
    m = json.load(open(path))
    assert m["schema_version"] == 1
    assert set(m["benches"]) == {"driver", "async"}
    assert m["benches"]["driver"]["path"] == "BENCH_driver.json"
    assert bench_key(m["benches"]["driver"]["path"]) == "driver"
