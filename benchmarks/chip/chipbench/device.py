"""The chip a run stands on: refuse anything but a TPU, name it, read its
peaks from ``peaks.json`` and its memory from the runtime."""
from __future__ import annotations

import json
from pathlib import Path

from chipbench.registry import HERE


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def require_tpu(chips: int):
    """The devices of this process, when the first is a TPU and there are at
    least ``chips`` of them."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoChip(f"JAX's first device is {dev.platform!r} ({dev.device_kind}), "
                     "not a TPU: this benchmark measures nothing else")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees {len(devices)}")
    return devices[:chips]


def peaks(kind: str) -> dict:
    """Published peaks of one chip of this ``device_kind``; an unknown kind
    is an error, never a default."""
    table = json.loads((HERE / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table["devices"][kind]


def describe(devices) -> dict:
    dev = devices[0]
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip of the run."""
    peaks_seen = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks_seen))


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed directory inside the
    checkout, whatever the environment says: the path is part of the key."""
    import jax

    path = Path(root) / ".jax_cache" / "bench"
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(path)
