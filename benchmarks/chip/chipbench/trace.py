"""Host phases, the profiler's trace, and its reduction to numbers.

The harness wraps each host phase of the measured window (``sample``,
``dispatch``, ``sync``, ...) in :class:`Phases`, which times it on the host
clock and writes it into the profiler's trace as a ``bench.<phase>``
annotation, on the same clock as the device's operations.  The window itself
is the ``bench.window`` annotation.  :func:`reduce_xplane` turns a trace into
the device's busy time inside that window, its top operations, and its idle
gaps labelled with the host phase they fell in.
"""
from __future__ import annotations

import collections
import contextlib
import time
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

PREFIX = "bench."
WINDOW = PREFIX + "window"
# the device lines whose events are the operations the chip ran and the
# program runs they belong to, and the host event that enqueues a run
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ENQUEUE = "DoEnqueueProgram"


class Phases:
    """Host spans of the harness: durations on the host clock, and the same
    spans as profiler annotations when a trace is being captured."""

    def __init__(self):
        self.seconds: Dict[str, List[float]] = collections.defaultdict(list)

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax

        with jax.profiler.TraceAnnotation(PREFIX + name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.seconds[name].append(time.perf_counter() - t0)

    def total(self, name: str) -> float:
        return float(sum(self.seconds.get(name, ())))


def union_length(intervals: Iterable[Tuple[int, int]]) -> Tuple[int, List[Tuple[int, int]]]:
    """Length of the union of ``[start, end)`` intervals, and the merged
    intervals in order."""
    merged: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return sum(e - s for s, e in merged), merged


def _clip(intervals, lo, hi):
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield s, e


def op_name(hlo: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def self_times(events: List[Tuple[str, int, int]]) -> collections.Counter:
    """Each operation's time less the time of the operations nested inside
    it (a ``while`` holds its body's operations on the same line)."""
    out = collections.Counter()
    stack: List[list] = []  # [name, start, end, nested time]

    def close(entry):
        out[entry[0]] += entry[2] - entry[1] - entry[3]

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += min(e, stack[-1][2]) - s
        stack.append([name, s, e, 0])
    while stack:
        close(stack.pop())
    return out


def reduce_events(
    device_ops: Dict[str, List[Tuple[str, int, int]]],
    host_spans: List[Tuple[str, int, int]],
    top: int = 10,
) -> Optional[dict]:
    """The reduction proper, on plain tuples (start and end in ns, device
    events already on the host's clock).

    ``device_ops`` maps each device used to its ``(op name, start, end)``
    events; ``host_spans`` are the harness's ``(name, start, end)``
    annotations.  Busy time is the union of the operations inside the
    window; the top operations are ranked by self time; every idle stretch
    is labelled with the host phase it fell in (``other`` where none).
    Returns ``None`` when the trace holds no window or no device
    operation."""
    windows = [(s, e) for n, s, e in host_spans if n == WINDOW]
    if not windows or not any(device_ops.values()):
        return None
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    phases = sorted((s, e, n[len(PREFIX):]) for n, s, e in host_spans
                    if n.startswith(PREFIX) and n != WINDOW)

    busy_ns, per_op = [], collections.Counter()
    idle_by_phase = collections.Counter()
    for events in device_ops.values():
        inside = [(n, s2, e2) for n, s, e in events for s2, e2 in _clip([(s, e)], lo, hi)]
        per_op.update(self_times(inside))
        length, merged = union_length((s, e) for _, s, e in inside)
        busy_ns.append(length)
        # idle stretches of this device, cut at the edges of the host phases
        edges = [lo] + [x for s, e in merged for x in (s, e)] + [hi]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge <= gs:
                continue
            covered = 0
            for ps, pe, name in phases:
                for s, e in _clip([(ps, pe)], gs, ge):
                    idle_by_phase[name] += e - s
                    covered += e - s
            if ge - gs - covered > 0:
                idle_by_phase["other"] += ge - gs - covered
    n_dev = len(device_ops)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_ns) / n_dev / 1e9,
        "device_ops": [[n, t / n_dev / 1e9] for n, t in per_op.most_common(top)],
        "idle_gaps": [[n, t / n_dev / 1e9] for n, t in idle_by_phase.most_common(top)],
    }


def _run_ids(events) -> Dict[int, int]:
    out = {}
    for ev in events:
        rid = dict(ev.stats).get("run_id")
        if rid is not None:
            out.setdefault(int(rid), int(ev.start_ns))
    return out


def read_xplane(path: Path, device_ids: Iterable[int] = (0,)):
    """``(device_ops, host_spans)`` of one ``.xplane.pb`` file, as
    :func:`reduce_events` takes them.

    Device planes are ``/device:TPU:<id>``, their operations the events of
    the ``XLA Ops`` line.  Their timestamps are on the device's clock, which
    the trace offsets from the host's: each program run (``run_id``) starts
    on the device no earlier than the host began to enqueue it
    (``DoEnqueueProgram``), so the device events are shifted by the least
    offset that keeps every run after its enqueue."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    wanted = {f"/device:TPU:{i}" for i in device_ids}
    device_ops: Dict[str, List[Tuple[str, int, int]]] = {}
    device_runs: Dict[str, Dict[int, int]] = {}
    host_spans: List[Tuple[str, int, int]] = []
    enqueued: Dict[int, int] = {}
    for plane in data.planes:
        if plane.name in wanted:
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(op_name(ev.name), int(ev.start_ns),
                             int(ev.start_ns + ev.duration_ns)) for ev in line.events]
                elif line.name == MODULES_LINE:
                    device_runs[plane.name] = _run_ids(line.events)
            device_ops[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = list(line.events)
                host_spans += [(ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                               for ev in events if ev.name.startswith(PREFIX)]
                enqueued.update(_run_ids(ev for ev in events if ev.name == ENQUEUE))
    for dev, ops in device_ops.items():
        runs = device_runs.get(dev, {})
        pairs = [enqueued[r] - start for r, start in runs.items() if r in enqueued]
        shift = max(pairs) if pairs else 0
        device_ops[dev] = [(n, s + shift, e + shift) for n, s, e in ops]
    return device_ops, host_spans


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"the profiler wrote no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_xplane(path: Path, device_ids: Iterable[int] = (0,)) -> Optional[dict]:
    return reduce_events(*read_xplane(path, device_ids))
