"""The device a run measures, and where its compiled programs are cached.

A run measures a TPU whose ``device_kind`` is in ``peaks.json``, with at
least as many chips as its cell asks for.  Anything else (no accelerator,
the CPU, an unknown kind, too few chips) raises ``NoChip``: there is no
fallback.
"""
from __future__ import annotations

import os

from harness.peaks import UnknownDevice, load_peaks
from harness.spec import CHECKOUT

#: Fixed in-checkout compile-cache path, used where JAX_COMPILATION_CACHE_DIR
#: is unset.  The path is part of the cache key, so it never carries a
#: temporary name, a process id or a time.
CACHE_DIR = os.path.join(CHECKOUT, ".cache", "perfbench", "jax")
#: The planner's plan cache, at a fixed path in the checkout.
PLAN_CACHE = os.path.join(CHECKOUT, ".cache", "perfbench", "plans.json")


class NoChip(RuntimeError):
    pass


def chips_for(n: int):
    """(devices, peaks) for a run on ``n`` chips; raises ``NoChip``."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:            # no backend could be initialised
        raise NoChip(f"JAX found no device: {e}") from None
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {dev.platform!r} "
                     f"({dev.device_kind!r})")
    try:
        peaks = load_peaks(dev.device_kind)
    except UnknownDevice as e:
        raise NoChip(str(e)) from None
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX sees {len(devices)}")
    return devices[:n], peaks


def configure_compile_cache() -> str:
    """JAX's persistent compilation cache at its fixed directory (or at
    ``JAX_COMPILATION_CACHE_DIR`` where set); every executable is cached,
    however quick its compile.  Copied from the program's
    ``repro.launch.compile_cache`` so that the benchmark owns the path."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts backend compilations and persistent-cache hits and misses
    from JAX's monitoring events, from construction on."""

    _EVENTS = {
        "/jax/compilation_cache/cache_hits": "cache_hits",
        "/jax/compilation_cache/cache_misses": "cache_misses",
    }

    def __init__(self):
        import jax

        self.counts = {"compiles": 0, "cache_hits": 0, "cache_misses": 0}
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **_kw) -> None:
        if event in self._EVENTS:
            self.counts[self._EVENTS[event]] += 1

    def _on_duration(self, event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.counts["compiles"] += 1

    def snapshot(self) -> dict:
        return dict(self.counts)
