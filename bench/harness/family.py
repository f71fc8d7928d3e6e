"""A configuration's model family picks its plain reference and its
operation and byte counts, by file.

``family`` ``dense`` (or no ``family``) takes ``harness/reference.py``
and ``flops.py``; any other family ``<f>`` takes
``harness/reference_<f>.py`` and ``flops_<f>.py``, so that a family
comes to the benchmark as new files only.  Each reference module has
``make_weights``, ``pack``, ``hidden``, ``logits`` and ``gap`` as
``reference.py`` has them; each counting module has ``flops_per_token``,
``decode_flops`` and ``decode_bytes`` as ``flops.py`` has them.  A family
whose file is not there fails on loading, and the error names the file.
"""

from __future__ import annotations

import importlib
from pathlib import Path
from typing import Dict

BENCH = Path(__file__).resolve().parents[1]
# module name (under bench/) of each kind, before the family's suffix
KINDS = {"reference": "harness.reference", "flops": "flops"}


def module(kind: str, cfg: Dict):
    """The ``kind`` module (``reference`` or ``flops``) of ``cfg``'s
    family."""
    fam = cfg.get("family", "dense")
    name = KINDS[kind] + ("" if fam == "dense" else f"_{fam}")
    path = BENCH / (name.replace(".", "/") + ".py")
    if not path.is_file():
        raise FileNotFoundError(
            f"family {fam!r} has no {kind} module: it needs "
            f"{path.relative_to(BENCH.parent)}")
    return importlib.import_module(name)
