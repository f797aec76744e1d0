"""The chip: refuse anything else, fix the compile cache, read memory."""

from __future__ import annotations

import os

from kdebench.spec import ROOT

#: JAX's persistent compile cache when the environment names none: a fixed
#: path inside the checkout (the path is part of the cache key).
CACHE_DIR = ROOT / "bench" / ".jax_cache"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_tpu(chips: int):
    """The devices of a run; raises :class:`NoChip` rather than fall back
    to the CPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform})")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def enable_compile_cache() -> str:
    """Persistent compile cache: ``$JAX_COMPILATION_CACHE_DIR`` when set
    (JAX reads it itself), else :data:`CACHE_DIR`.  Every program is
    cached, so only a cell's first run in a checkout compiles."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def describe(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip (0 where not reported)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


class CompileCounter:
    """XLA programs compiled, or loaded from the persistent cache, in this
    process: JAX times both under one monitoring event."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.n += 1
