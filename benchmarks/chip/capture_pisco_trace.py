#!/usr/bin/env python3
"""Trace a fleet cell's window and split it by the program's own spans and
scopes:

    python3 benchmarks/chip/capture_pisco_trace.py --workload fleet.paper-mlp.ring4096 \\
        --seed <n> --seconds <s> [--agents <a>] [--out <dir>]

Builds the cell's timed path as its runner does (``runners/train.py``:
``build``, ``setup``, ``window``), profiles the window, compiles the block
once more for its instructions' ``op_name``, and prints one JSON object: the
host sampler's gather and put (``repro.sample.*`` spans), the device self
time under each named scope of the PISCO round, and busy time, each per
round; the window's compiles; and the idle time by program span.
``--agents`` runs the cell's traffic with fewer agents.  ``--out`` also
writes ``<dir>/pisco_trace.xplane.pb``, the trace trimmed to what the
reductions read (:func:`trim`), and ``<dir>/pisco_expected.json`` (what the
host measured, the block's ``op_name`` of every operation in the trace, and
the reduction), which the trace reduction's tests read.
"""
import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

T_START = time.perf_counter()
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

SPANS = ("repro.sample.gather", "repro.sample.put")


def batch_specs(sampler, rounds: int):
    """Shapes of one block's ``(flags, local, comm)``, as the window
    dispatches them (``RoundSampler.sample_block``'s contract)."""
    import jax

    d, sds = sampler.data, jax.ShapeDtypeStruct
    a, b, t = d.n_agents, sampler.b, sampler.t_o
    fx = d.x_train.shape[2:]
    dx, dy = (jax.dtypes.canonicalize_dtype(v.dtype) for v in (d.x_train, d.y_train))
    local = (sds((rounds, t, a, b, *fx), dx), sds((rounds, t, a, b), dy))
    comm = (sds((rounds, a, b, *fx), dx), sds((rounds, a, b), dy))
    return sds((rounds,), bool), local, comm


def block_op_names(prog, state) -> dict:
    """``{module: {instruction: op_name}}`` of the compiled block."""
    from chipbench.split import hlo_op_names

    specs = batch_specs(prog.sampler, prog.rounds_per_block)
    module, names = hlo_op_names(prog.block_fn.lower(state, *specs).compile().as_text())
    return {module: names}


def trim(src: Path, dest: Path) -> None:
    """Write ``src`` to ``dest`` with only what the reductions read: the
    device planes whole; of the host planes the ``bench.*`` and
    ``repro.*`` spans and the program enqueues.  The compiled programs' HLO
    (``/host:metadata``) and the host's other events go."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    from chipbench.split import SPAN
    from chipbench.trace import ENQUEUE, PREFIX

    space = xplane_pb2.XSpace()
    space.ParseFromString(src.read_bytes())
    planes = [p for p in space.planes if p.name != "/host:metadata"]
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        wanted = {i for i, m in plane.event_metadata.items()
                  if m.name.startswith((PREFIX, SPAN)) or m.name == ENQUEUE}
        for i in set(plane.event_metadata) - wanted:
            del plane.event_metadata[i]
        for line in plane.lines:
            events = [ev for ev in line.events if ev.metadata_id in wanted]
            del line.events[:]
            line.events.extend(events)
        lines = [line for line in plane.lines if line.events]
        del plane.lines[:]
        plane.lines.extend(lines)
    del space.planes[:]
    space.planes.extend(planes)
    dest.write_bytes(space.SerializeToString())


def report(summary, win: dict, phases, compiled, bytes_per_round: int) -> dict:
    from chipbench.split import SCOPES, per_round_ms

    rounds = win["rounds"]
    out = {"rounds": rounds, "window_s": win["window_s"],
           "host_sample_ms": 1000.0 * phases.total("sample") / rounds,
           "window_compiles": getattr(compiled, "compiles", None),
           "window_compile_s": compiled.seconds}
    for span in SPANS:
        ms = per_round_ms(summary, "program_spans", span, rounds)
        out[span + "_ms"] = ms
        out[span + "_GB_per_s"] = bytes_per_round / ms / 1e6 if ms else None
    for scope in SCOPES + ("other",):
        out[scope + "_device_ms"] = per_round_ms(summary, "device_scopes", scope, rounds)
    if summary:
        out["busy_ms"] = 1000.0 * summary["busy_s"] / rounds
        out["other_ops_s"] = summary.get("other_ops")
        out["idle_spans_s"] = summary["idle_spans"]
        out["idle_gaps_s"] = dict(summary["idle_gaps"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="fleet.paper-mlp.ring4096")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--agents", type=int, help="the traffic's agents, cut to this many")
    ap.add_argument("--out", help="write the trace and what the host measured here")
    ap.add_argument("--off-chip", action="store_true",
                    help="run on whatever JAX finds (a rehearsal: no device trace)")
    args = ap.parse_args(argv)

    from chipbench import device, registry

    root = HERE.parents[1]
    cell = registry.load_cell(root, args.workload)
    if args.agents:
        cell.traffic = dict(cell.traffic, agents=args.agents)
    sys.path.insert(0, str(root / "src"))

    import jax

    from chipbench.split import read_xplane, reduce_xplane
    from chipbench.trace import Phases, find_xplane
    from repro.obs.profile import track_compile_time

    if not args.off_chip:
        device.enable_compile_cache(root)
        device.require_tpu(cell.chips)
    train = cell.runner
    prog = train.build(cell, args.seed)
    sess = train.setup(prog)
    jax.block_until_ready(sess.state)
    setup_s = time.perf_counter() - T_START
    phases = Phases()
    tmp = tempfile.mkdtemp(prefix="chipbench-pisco-")
    jax.profiler.start_trace(tmp)
    try:
        with track_compile_time() as compiled:
            win = train.window(sess, args.seconds, phases)
    finally:
        jax.profiler.stop_trace()
    op_names = block_op_names(prog, sess.state)
    path = find_xplane(tmp)
    summary = reduce_xplane(path, range(cell.chips), op_names=op_names)
    _, local, comm = batch_specs(prog.sampler, 1)
    nbytes = sum(s.size * s.dtype.itemsize for s in (*local, *comm))
    out = dict(report(summary, win, phases, compiled, nbytes), setup_s=setup_s,
               workload=cell.name, agents=cell.traffic["agents"], seed=args.seed)
    if args.out:
        dest = Path(args.out)
        dest.mkdir(parents=True, exist_ok=True)
        trim(path, dest / "pisco_trace.xplane.pb")
        seen = {n for ops in read_xplane(path, range(cell.chips))[0].values() for n, _, _ in ops}
        expected = {
            "phases": {k: phases.total(k) for k in phases.seconds},
            "rounds": win["rounds"],
            "op_names": {m: {k: v for k, v in names.items() if k in seen}
                         for m, names in op_names.items()},
            "reduction": summary,
        }
        (dest / "pisco_expected.json").write_text(json.dumps(expected, indent=1))
    shutil.rmtree(tmp, ignore_errors=True)
    print(f"chipbench: window compiles {out['window_compiles']} "
          f"({out['window_compile_s']!r} s)", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
