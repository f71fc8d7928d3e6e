"""The device a run stands on, compile counting and the compile cache."""

from __future__ import annotations

import os
import sys
from typing import Any, Dict, Optional


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def stamp(chips: Optional[int]) -> Dict[str, Any]:
    """``platform``, ``kind`` and ``count`` as JAX reports them; raises
    :class:`NoAccelerator` without a TPU or with fewer chips than
    ``chips``.  Never falls back to the CPU; ``chips`` ``None`` (the
    tests on the CPU) checks nothing."""
    import jax

    devs = jax.devices()
    if chips is None:
        pass
    elif devs[0].platform != "tpu":
        raise NoAccelerator(f"no TPU: JAX found {devs[0].platform}")
    elif len(devs) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips, JAX found "
                            f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes() -> int:
    """Peak bytes in use on the fullest chip of this process."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


class CompileClock:
    """Counts the programs JAX compiles and the seconds it spends on them,
    reads of the persistent compile cache included.  (A copy of the clock
    in the repository's ``chip_smoke.py``.)"""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        self.programs = 0

    def __call__(self, event: str, duration: float, **_: Any) -> None:
        if event == self.EVENT:
            self.seconds += duration
            self.programs += 1

    def __enter__(self) -> "CompileClock":
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self)


def enable_cache() -> str:
    """The program's persistent compile cache (``<checkout>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` is set), with every program
    cached however fast it compiled, so that a warm set-up compiles
    nothing.  Returns the directory and prints how full it is."""
    import jax

    from repro.launch.chip import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    n = len(os.listdir(path)) if os.path.isdir(path) else 0
    print(f"compile cache {path}: {n} entries at start"
          + (" (first run in this checkout: set-up compiles)" if n == 0
             else ""), file=sys.stderr, flush=True)
    return path
