"""Process set-up for running on the chip: the persistent compile cache
and the libtpu environment that gives one process one chip.

Nothing here runs on import.  Entry points call
:func:`enable_compile_cache` first thing; the fleet's parent passes
:func:`one_chip_env` to each worker it spawns, so the child starts with
its chip assignment before it imports JAX.
"""

from __future__ import annotations

import os
import socket
from pathlib import Path
from typing import Dict

# <checkout>/.jax_cache: fixed, because the path is part of what the
# cache is keyed on — a directory that moves never hits
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its path.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed.  Otherwise the cache goes to :data:`CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def free_port() -> int:
    """A localhost TCP port that is free right now."""
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def one_chip_env(chip: int, port: int) -> Dict[str, str]:
    """libtpu variables that confine a process to chip ``chip`` of its host.

    A per-process chip bound that is a subset of the host's chips is what
    lets several processes load libtpu at once, one chip each; every
    process also needs its own slice-builder port.
    """
    return {
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_VISIBLE_CHIPS": str(int(chip)),
        "TPU_PROCESS_PORT": str(int(port)),
        "TPU_PROCESS_ADDRESSES": f"localhost:{int(port)}",
    }
