"""The program's own spans and scopes in a traced window.

The program writes two kinds of marks into the profiler's trace:

- host spans, ``repro.<name>`` annotations around host work
  (``repro.obs.trace.span``): ``repro.sample.gather`` and
  ``repro.sample.put`` inside the harness's ``bench.sample``;
- named scopes on the PISCO round (``jax.named_scope``), which reach the
  trace only through each HLO instruction's ``op_name`` metadata:
  ``pisco.local``, ``pisco.comm``, ``mix`` and ``pisco.metrics``.

:func:`reduce_split` adds to :func:`chipbench.trace.reduce_events`'s summary,
all inside ``bench.window``: ``program_spans`` (host seconds by span name),
``device_scopes`` (device self time by innermost scope, ``other`` where an
operation has none), ``other_ops`` (the largest operations in ``other``)
and ``idle_spans`` (device idle time by the innermost program span it fell
in, ``other`` where none).  The reduction of the other keys is
:mod:`chipbench.trace`'s, untouched.

The trace does not carry ``op_name``: its operations are named by HLO
instruction only, within the program (``XLA Modules`` event) that ran them.
The map from instruction to ``op_name`` comes from the compiled program's
text (:func:`hlo_op_names`), one per program, so an instruction of another
program that shares a name is never taken for one of the block's.
"""
from __future__ import annotations

import bisect
import collections
import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

from chipbench.trace import (
    ENQUEUE,
    MODULES_LINE,
    OPS_LINE,
    PREFIX,
    WINDOW,
    _clip,
    _run_ids,
    op_name,
    reduce_events,
    self_times,
    union_length,
)

SPAN = "repro."
# the round's named scopes (``core/pisco.py``); an operation belongs to the
# innermost one in its op_name
SCOPES = ("pisco.local", "pisco.comm", "mix", "pisco.metrics")
OTHER = "other"

Events = List[Tuple[str, int, int]]

_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")


def scope_of(name: Optional[str]) -> str:
    """The innermost of :data:`SCOPES` in an ``op_name``, or ``other``.

    A fused instruction's ``op_name`` may list several paths joined by
    ``;``: the first is its own.  A scope entered inside a transformation
    reads ``vmap(pisco.local)``; the wrapper is peeled off."""
    if name:
        for part in reversed(name.split(";", 1)[0].split("/")):
            part = part.rsplit("(", 1)[-1].rstrip(")")
            if part in SCOPES:
                return part
    return OTHER


def hlo_op_names(text: str) -> Tuple[str, Dict[str, str]]:
    """The module name of a compiled program's HLO text, and the ``op_name``
    of each of its instructions.  A fusion without metadata of its own
    takes that of its fused computation's root."""
    module, comp = "", None
    names: Dict[str, str] = {}
    calls: Dict[str, str] = {}
    roots: Dict[str, str] = {}
    for line in text.splitlines():
        if line.startswith("HloModule "):
            module = line.split()[1].rstrip(",")
            continue
        m = _INSTRUCTION.match(line)
        if m is None:
            if line.endswith("{") and not line.startswith(" "):
                comp = line.split()[1 if line.startswith("ENTRY ") else 0].lstrip("%")
            continue
        name, op = m.group(2), _OP_NAME.search(line)
        if op:
            names[name] = op.group(1)
            if m.group(1) and comp:
                roots[comp] = op.group(1)
        called = _CALLS.search(line)
        if called:
            calls[name] = called.group(1)
    for name, comp in calls.items():
        if name not in names and comp in roots:
            names[name] = roots[comp]
    return module, names


def module_of(event_name: str) -> str:
    """``jit_block_fn(1234)`` (an ``XLA Modules`` event) -> ``jit_block_fn``."""
    return event_name.rsplit("(", 1)[0]


def read_xplane(path: Path, device_ids: Iterable[int] = (0,)):
    """``(device_ops, host_spans, device_modules)`` of one ``.xplane.pb``.

    As :func:`chipbench.trace.read_xplane`, with the program's ``repro.*``
    spans among the host spans, and each device's program runs
    ``(module, start, end)``, on the host's clock by the same shift as its
    operations."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    wanted = {f"/device:TPU:{i}" for i in device_ids}
    device_ops: Dict[str, Events] = {}
    device_modules: Dict[str, Events] = {}
    device_runs: Dict[str, Dict[int, int]] = {}
    host_spans: Events = []
    enqueued: Dict[int, int] = {}
    for plane in data.planes:
        if plane.name in wanted:
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(op_name(ev.name), int(ev.start_ns),
                             int(ev.start_ns + ev.duration_ns)) for ev in line.events]
                elif line.name == MODULES_LINE:
                    events = list(line.events)
                    device_runs[plane.name] = _run_ids(events)
                    modules += [(module_of(ev.name), int(ev.start_ns),
                                 int(ev.start_ns + ev.duration_ns)) for ev in events]
            device_ops[plane.name], device_modules[plane.name] = ops, modules
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = list(line.events)
                host_spans += [(ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                               for ev in events if ev.name.startswith((PREFIX, SPAN))]
                enqueued.update(_run_ids(ev for ev in events if ev.name == ENQUEUE))
    for dev in device_ops:
        runs = device_runs.get(dev, {})
        pairs = [enqueued[r] - start for r, start in runs.items() if r in enqueued]
        shift = max(pairs) if pairs else 0
        device_ops[dev] = [(n, s + shift, e + shift) for n, s, e in device_ops[dev]]
        device_modules[dev] = [(n, s + shift, e + shift) for n, s, e in device_modules[dev]]
    return device_ops, host_spans, device_modules


def _label(events: Events, runs: Events) -> List[Tuple[Tuple[Optional[str], str], int, int]]:
    """Each operation keyed by ``(module, name)``: the program run whose
    span holds its start (``None`` where no run does)."""
    runs = sorted(runs, key=lambda r: r[1])
    starts = [s for _, s, _ in runs]
    out = []
    for name, s, e in events:
        i = bisect.bisect_right(starts, s) - 1
        module = runs[i][0] if i >= 0 and runs[i][2] >= s else None
        out.append(((module, name), s, e))
    return out


def _innermost(spans: Events, lo: int, hi: int) -> Events:
    """``[lo, hi)`` cut into pieces, each named by the innermost span that
    covers it (the one that began last), ``other`` where none does."""
    cuts = sorted({lo, hi} | {x for _, s, e in spans for x in (s, e) if lo < x < hi})
    pieces = []
    for a, b in zip(cuts, cuts[1:]):
        covering = [(s, -e, n) for n, s, e in spans if s <= a and e >= b]
        pieces.append((max(covering)[2] if covering else OTHER, a, b))
    return pieces


def reduce_split(
    device_ops: Dict[str, Events],
    host_spans: Events,
    device_modules: Optional[Dict[str, Events]] = None,
    op_names: Optional[Dict[str, Dict[str, str]]] = None,
    top: Optional[int] = 10,
) -> Optional[dict]:
    """``program_spans``, ``device_scopes``, ``other_ops`` and
    ``idle_spans`` of a window (module docstring).  ``other_ops`` are the
    ``top`` operations in ``other`` by self time (all where ``top`` is
    ``None``), as ``<module>/<instruction>``.  ``op_names`` maps a module
    name to its instructions' ``op_name`` (:func:`hlo_op_names`); without
    it there is no ``device_scopes`` nor ``other_ops``.  Device seconds are averaged over the devices, as
    :func:`chipbench.trace.reduce_events` does.  ``None`` where that
    reduction finds no window or no operation."""
    windows = [(s, e) for n, s, e in host_spans if n == WINDOW]
    if not windows or not any(device_ops.values()):
        return None
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    spans = [(n, s, e) for n, s, e in host_spans if n.startswith(SPAN)
             for s, e in _clip([(s, e)], lo, hi)]
    program = collections.Counter()
    for n, s, e in spans:
        program[n] += e - s
    pieces = _innermost(spans, lo, hi)
    starts = [a for _, a, _ in pieces]
    device_modules = device_modules or {}
    scopes, idle, unscoped = collections.Counter(), collections.Counter(), collections.Counter()
    for dev, events in device_ops.items():
        inside = [(n, s2, e2) for n, s, e in events for s2, e2 in _clip([(s, e)], lo, hi)]
        if op_names is not None:
            for (module, name), t in self_times(_label(inside, device_modules.get(dev, []))).items():
                scope = scope_of(op_names.get(module, {}).get(name))
                scopes[scope] += t
                if scope == OTHER:
                    unscoped[f"{module}/{name}"] += t
        _, merged = union_length((s, e) for _, s, e in inside)
        edges = [lo] + [x for s, e in merged for x in (s, e)] + [hi]
        for gs, ge in zip(edges[::2], edges[1::2]):
            i = max(bisect.bisect_right(starts, gs) - 1, 0)
            while i < len(pieces) and pieces[i][1] < ge:
                label, a, b = pieces[i]
                if min(b, ge) > max(a, gs):
                    idle[label] += min(b, ge) - max(a, gs)
                i += 1
    n_dev = len(device_ops)
    out = {"program_spans": {n: t / 1e9 for n, t in program.items()},
           "idle_spans": {n: t / n_dev / 1e9 for n, t in idle.items()}}
    if op_names is not None:
        out["device_scopes"] = {n: t / n_dev / 1e9 for n, t in scopes.items()}
        out["other_ops"] = [[n, t / n_dev / 1e9] for n, t in unscoped.most_common(top)]
    return out


def reduce_xplane(path: Path, device_ids: Iterable[int] = (0,),
                  op_names: Optional[Dict[str, Dict[str, str]]] = None) -> Optional[dict]:
    """:func:`chipbench.trace.reduce_events`'s summary of a trace, with the
    keys of :func:`reduce_split` added."""
    ops, spans, modules = read_xplane(path, device_ids)
    summary = reduce_events(ops, spans)
    if summary is not None:
        summary.update(reduce_split(ops, spans, modules, op_names))
    return summary


def per_round_ms(summary: Optional[dict], key: str, name: str, rounds: int) -> Optional[float]:
    """Milliseconds per round of one entry of ``summary[key]``, or ``None``
    where the summary does not hold it."""
    if not summary or not rounds or name not in summary.get(key, {}):
        return None
    return 1000.0 * summary[key][name] / rounds
