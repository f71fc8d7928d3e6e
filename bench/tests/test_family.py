"""A configuration's family picks the reference and the counts by file:
the dense family reads as it did before the choice, and a family with no
files fails on loading, naming the file it needs."""

import numpy as np
import pytest

import tiny
import flops
from harness import correct, family, local, reference


def picked():
    rng = np.random.default_rng(7)
    return [(rng.integers(0, 500, 20).tolist(),
             rng.integers(0, 500, 30).tolist()),
            (rng.integers(0, 500, 9).tolist(),
             rng.integers(0, 500, 50).tolist())]


def test_dense_reads_as_before():
    cfg = tiny.config()
    assert family.module("reference", cfg) is reference
    assert family.module("flops", cfg) is flops
    no_key = {k: v for k, v in cfg.items() if k != "family"}
    assert family.module("flops", no_key) is flops
    # the gaps and counts the harness read before it chose by family
    got = correct.read_gaps(cfg, 12345, picked(), 128, control=True)
    assert got == {"tokens": 80, "logit_gap": 5.494808673858643,
                   "control_gap": 0.2892608642578125}
    sc2 = tiny.json.loads((tiny.BENCH / "configs" / "starcoder2-7b.json")
                          .read_text())
    count = family.module("flops", sc2)
    assert count.flops_per_token(sc2, 99) == 7428243456.0
    assert count.decode_bytes(sc2, [9, 99, 500]) == 7419507712
    assert count.decode_flops(sc2, [9, 99, 500]) == 22376448000.0


def test_unknown_family_fails_naming_its_file():
    cfg = dict(tiny.config(), family="ssm")
    with pytest.raises(FileNotFoundError,
                       match=r"bench/harness/reference_ssm\.py"):
        correct.read_gaps(cfg, 1, picked(), 128)
    with pytest.raises(FileNotFoundError, match=r"bench/flops_ssm\.py"):
        family.module("flops", cfg)


def test_arch_of_builds_every_field_and_the_spec_blocks():
    from repro.configs.base import MLASpec, MoESpec

    cfg = dict(tiny.config(), rope_dim=8,
               mla={"q_lora_rank": 32, "kv_lora_rank": 16,
                    "qk_nope_dim": 8, "qk_rope_dim": 8, "v_head_dim": 16},
               moe={"n_routed": 8, "n_shared": 1, "top_k": 2,
                    "d_expert": 32, "n_dense_layers": 1})
    arch = local.arch_of(cfg)
    assert arch.rope_dim == 8
    assert arch.mla == MLASpec(32, 16, 8, 8, 16)
    assert isinstance(arch.moe, MoESpec) and arch.moe.top_k == 2
    # the dense file builds the same architecture it built before
    sc2 = local.arch_of(tiny.config())
    assert (sc2.mla, sc2.moe, sc2.n_layers, sc2.param_dtype) == (
        None, None, 2, "bfloat16")
