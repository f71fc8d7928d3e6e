"""chip_smoke.py's control flow on the CPU, at ``reduced()`` size.

The script's phases run here exactly as on the chip — build through
``Serve.local``, mid-decode checkpoint, restore into a fresh stack, the
forward check, the fleet's kill run — only the device assertion that
``main`` makes is left out.  ``main`` itself must refuse to run without
a TPU, in the checkout and with the script alone.
"""

from __future__ import annotations

import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_phases_at_reduced_size(smoke, tmp_path):
    arch = smoke.published_arch().reduced()
    assert arch.param_dtype == "bfloat16"
    facts = smoke.run_smoke(arch, tmp_path)
    assert facts["identical"]
    assert facts["tokens"] == smoke.N_REQUESTS * smoke.MAX_NEW
    assert facts["first_token"][0] == facts["first_token"][1]
    assert facts["pool_pages"] == smoke.POOL_PAGES


def test_smoke_fleet_kill_at_reduced_size(smoke, tmp_path):
    facts = smoke.run_fleet(smoke.published_arch().reduced(), tmp_path,
                            workers=2, n_requests=4)
    assert facts["identical"] and facts["migrated"] >= 1
    assert [d["visible_chips"] for d in facts["devices"]] == ["0", "1"]


@pytest.mark.parametrize("where", ["checkout", "script_alone"])
def test_main_fails_without_tpu(where, tmp_path):
    script = ROOT / "chip_smoke.py"
    if where == "script_alone":
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "JAX_COMPILATION_CACHE_DIR")}
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    for argv in ([], ["--fleet"]):
        res = subprocess.run([sys.executable, str(script), *argv],
                             cwd=script.parent, env=env, capture_output=True,
                             text=True, timeout=120)
        assert res.returncode != 0, res.stdout
        assert '"ok"' not in res.stdout
