"""The device a run measures, JAX's compile cache, and compile counts."""
from __future__ import annotations

import os


class NoAccelerator(Exception):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def accelerator_devices(chips: int):
    """The first ``chips`` accelerator devices; raises
    :class:`NoAccelerator` rather than fall back to the CPU."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform not in ("tpu", "gpu"):
        raise NoAccelerator(f"JAX found no accelerator (platform "
                            f"{platform!r})")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips, JAX found "
                            f"{len(devices)}")
    return devices[:chips]


def use_compile_cache(root: str) -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is where JAX keeps it and
    nothing is set here; otherwise it goes to ``<root>/.jax_cache``, a
    fixed path inside the checkout (the path ``chip_smoke.py`` uses).
    Every program is cached, however quickly it compiled, so a second
    run of a cell compiles nothing.
    """
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = env or os.path.join(root, ".jax_cache")
    if not env:
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(devices) -> dict:
    """Platform, kind and count as JAX reports them, and the peak bytes
    in use on the fullest device (where the backend reports it)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": peak}


# JAX's own compile events (jax._src.dispatch): a trace of a function to
# a jaxpr, and a backend compile (or a load from the persistent cache)
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Counts JAX's trace and compile events while it is open.

    ``compiles`` counts backend compiles and loads from the persistent
    cache; ``compile_s`` their seconds; ``traces`` the jaxpr traces.
    """

    def __init__(self):
        self.compiles = 0
        self.traces = 0
        self.compile_s = 0.0

    def _on_duration(self, event: str, seconds: float, **_) -> None:
        if event == _COMPILE_EVENT:
            self.compiles += 1
            self.compile_s += seconds
        elif event == _TRACE_EVENT:
            self.traces += 1

    def snapshot(self) -> tuple[int, int]:
        return self.compiles, self.traces

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        return False
