"""Device self time of a traced window by the innermost named scope, the
PISCO round's (:data:`chipbench.split.SCOPES`) and the model's own.

The language models mark the parts of their layers with ``jax.named_scope``
(``models/mamba2.py``, ``models/transformer.py``): the embedding, each
block's input projection, causal conv, SSD and gated norm with the output
projection, and the head with the loss.  Inside the round they nest under
``pisco.local`` or ``pisco.comm``, forward and backward alike, so the
innermost scope of an operation is the model's where it has one.  An
operation in none of :data:`SCOPES` is ``other``.

As in :mod:`chipbench.split`, an operation is found by its HLO instruction
within the program run that holds it, and its ``op_name`` comes from the
compiled block's text (``capture_pisco_trace.block_op_names``).
"""
from __future__ import annotations

import collections
from pathlib import Path
from typing import Dict, Iterable, Optional

from chipbench import split
from chipbench.trace import WINDOW, _clip, reduce_events, self_times

MODEL_SCOPES = ("lm.embed", "lm.head", "mamba2.in_proj", "mamba2.conv", "mamba2.ssd",
                "mamba2.out_proj")
SCOPES = split.SCOPES + MODEL_SCOPES


def scope_in(name: Optional[str], scopes: Iterable[str] = SCOPES) -> str:
    """The innermost of ``scopes`` in an ``op_name``, or ``other``; a
    transformation's wrapper (``transpose(jvp(mamba2.ssd))``) is peeled off,
    and of a fused instruction's paths the first is its own."""
    if name:
        for part in reversed(name.split(";", 1)[0].split("/")):
            part = part.rsplit("(", 1)[-1].rstrip(")")
            if part in scopes:
                return part
    return split.OTHER


def device_scopes(device_ops, host_spans, device_modules, op_names: Dict[str, Dict[str, str]],
                  scopes: Iterable[str] = SCOPES) -> Dict[str, float]:
    """Device seconds inside ``bench.window`` by innermost scope, averaged
    over the devices; empty where the trace holds no window."""
    windows = [(s, e) for n, s, e in host_spans if n == WINDOW]
    if not windows or not device_ops:
        return {}
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    scopes = tuple(scopes)
    out = collections.Counter()
    for dev, events in device_ops.items():
        inside = [(n, s2, e2) for n, s, e in events for s2, e2 in _clip([(s, e)], lo, hi)]
        labelled = split._label(inside, (device_modules or {}).get(dev, []))
        for (module, name), t in self_times(labelled).items():
            out[scope_in(op_names.get(module, {}).get(name), scopes)] += t
    return {n: t / len(device_ops) / 1e9 for n, t in out.items()}


def reduce_xplane(path: Path, device_ids: Iterable[int],
                  op_names: Dict[str, Dict[str, str]]) -> Optional[dict]:
    """:func:`chipbench.trace.reduce_events`'s summary of a trace, with
    ``device_scopes`` over :data:`SCOPES` added."""
    ops, spans, modules = split.read_xplane(path, device_ids)
    summary = reduce_events(ops, spans)
    if summary is not None:
        summary["device_scopes"] = device_scopes(ops, spans, modules, op_names)
    return summary
