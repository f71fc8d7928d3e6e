"""What the fleet driver runs in each worker process: the program's own
``worker_main``, with the harness's instruments around it.

``Serve.fleet`` spawns ``repro.serve.fleet.worker.worker_main``; the
fleet driver puts :func:`main` in its place for the launch (``fleet.py``),
and ``main`` calls the program's ``worker_main`` unchanged.  Around it:

* where the parent asks for a platform (``PLATFORM_ENV``), the worker
  looks at its device first and, on another, exits before it builds
  anything, saying so on its pipe;
* JAX's persistent compile cache keeps every program, however fast it
  compiled, so that a warm set-up compiles nothing;
* every ``stats`` reply the worker sends carries a ``bench`` entry: the
  programs compiled so far, the device's peak bytes in use and the bytes
  this process has written.
"""

from __future__ import annotations

import os
from typing import Any, Dict

PLATFORM_ENV = "BENCH_FLEET_PLATFORM"


def written_bytes() -> int:
    """Bytes this process has written to storage, as the kernel counts
    them (0 where it does not)."""
    try:
        with open("/proc/self/io") as f:
            for line in f:
                if line.startswith("write_bytes:"):
                    return int(line.split()[1])
    except OSError:
        pass
    return 0


class _Pipe:
    """The worker's end of its pipe, with ``bench`` added to each
    ``stats`` reply."""

    def __init__(self, conn, extra):
        self._conn, self._extra = conn, extra

    def send(self, msg: Dict[str, Any]) -> None:
        if msg.get("op") == "stats":
            msg = dict(msg, bench=self._extra())
        self._conn.send(msg)

    def poll(self, timeout: float = 0.0) -> bool:
        return self._conn.poll(timeout)

    def recv(self) -> Any:
        return self._conn.recv()

    def close(self) -> None:
        self._conn.close()


def main(conn, spec) -> None:
    import jax

    from harness.device import CompileClock
    from repro.serve.fleet import worker as W

    want = os.environ.get(PLATFORM_ENV)
    dev = jax.devices()[0]
    if want and dev.platform != want:
        conn.send({"op": "no_accelerator", "platform": dev.platform})
        conn.close()
        return
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    clock = CompileClock().__enter__()

    def extra() -> Dict[str, Any]:
        stats = dev.memory_stats() or {}
        return {"compiles": clock.programs, "compile_s": clock.seconds,
                "peak_bytes": int(stats.get("peak_bytes_in_use", 0)),
                "written_bytes": written_bytes()}

    W.worker_main(_Pipe(conn, extra), spec)
