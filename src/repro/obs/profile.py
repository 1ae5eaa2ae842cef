"""Profiler hooks: ``jax.profiler`` capture + compile-seconds attribution.

Two instruments, both safe to leave in production code paths:

* :func:`profile_capture` — context manager around ``jax.profiler.trace``.
  ``outdir=None`` (the default everywhere) is a strict no-op; a capture that
  was asked for and cannot start raises, so a run never exits 0 without the
  trace it was told to write.

* :func:`track_compile_time` — counts the XLA compilations inside the
  ``with`` body and measures their seconds, via ``jax.monitoring``'s event-duration listeners (the
  channel JAX's own internal telemetry uses; events fire with names like
  ``/jax/core/compile/backend_compile_duration``).  ``jax.monitoring`` has
  no public unregister, so one module-level listener is installed lazily on
  first use and fans out to a stack of active :class:`CompileStats` —
  nesting works, and an empty stack makes the listener a dict lookup + no-op.
  On jax builds without ``jax.monitoring`` the stats come back with
  ``supported=False`` and zero seconds.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Dict, Iterator, List, Optional


@dataclasses.dataclass
class CompileStats:
    """Compilations observed while a ``track_compile_time`` block ran:
    ``compiles`` backend compiles (a load from the persistent compilation
    cache counts as one) taking ``seconds`` in all."""

    seconds: float = 0.0
    compiles: int = 0
    events: Dict[str, float] = dataclasses.field(default_factory=dict)
    supported: bool = True

    def _observe(self, event: str, duration_s: float) -> None:
        self.events[event] = self.events.get(event, 0.0) + duration_s
        # backend_compile is a sub-phase of the jaxpr-trace events; summing
        # all "/compile/" events would double-count, so track the dominant
        # top-level one for `seconds` and keep the full split in `events`.
        if event.endswith("backend_compile_duration"):
            self.seconds += duration_s
            self.compiles += 1


_ACTIVE: List[CompileStats] = []
_LISTENER_INSTALLED = False


def _listener(event: str, duration_s: float, **kwargs) -> None:
    if "compile" in event and _ACTIVE:
        _ACTIVE[-1]._observe(event, duration_s)


def _ensure_listener() -> bool:
    global _LISTENER_INSTALLED
    if _LISTENER_INSTALLED:
        return True
    try:
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(_listener)
    except Exception:  # pragma: no cover - old/stripped jax builds
        return False
    _LISTENER_INSTALLED = True
    return True


@contextlib.contextmanager
def track_compile_time() -> Iterator[CompileStats]:
    """Yield a :class:`CompileStats` counting the compiles inside the block
    and their seconds.  Zero overhead beyond a listener dict update per
    compile event; nesting attributes each compile to the innermost block."""
    stats = CompileStats(supported=_ensure_listener())
    _ACTIVE.append(stats)
    try:
        yield stats
    finally:
        _ACTIVE.remove(stats)


@contextlib.contextmanager
def profile_capture(outdir: Optional[str]) -> Iterator[None]:
    """Capture a ``jax.profiler`` trace of the block into ``outdir``.

    ``outdir=None`` is a no-op (the default wiring everywhere), so call
    sites need no conditional.  The resulting directory opens in
    TensorBoard's profile plugin or via Perfetto's XPlane importer.
    """
    if not outdir:
        yield
        return
    import jax

    with jax.profiler.trace(outdir):
        yield
