"""A whole run on the CPU at a tiny size, with the device look skipped:
sound, it comes out correct; with the timed path broken underneath, in
each way a serving cell can break, it comes out not correct."""

import time

import numpy as np
import pytest

import tiny
import run


def installer(fault):
    """An ``on_built`` hook that wraps the step dispatch with ``fault``."""
    def install(srv):
        sched = srv.scheduler
        inner = sched._paged_fn
        calls = [0]

        def broken(p, pools, tables, pos, toks):
            calls[0] += 1
            return fault(inner, calls[0], p, pools, tables, pos, toks)

        sched._paged_fn = broken
    return install


def state_unchanged(inner, n, p, pools, tables, pos, toks):
    out, _ = inner(p, pools, tables, pos, toks)
    return out, pools                   # the step's KV writes are dropped


def half_batch(inner, n, p, pools, tables, pos, toks):
    b = toks.shape[0]
    if b == 1:
        return inner(p, pools, tables, pos, toks)
    h = b // 2
    tables = np.asarray(tables).copy()
    tables[h:] = 0                      # the second half is not computed:
    out, pools = inner(p, pools, tables, pos, toks)
    out = np.asarray(out).copy()
    out[h:] = out[:b - h]               # its lanes get the first half's
    return out, pools


def token_altered(inner, n, p, pools, tables, pos, toks):
    out, pools = inner(p, pools, tables, pos, toks)
    if toks.shape[0] > 1 and n % 8 == 0:
        out = (np.asarray(out) + 1) % tiny.config()["vocab_size"]
    return out, pools


def one_run(on_built=None, seed=2 ** 31 + 77):
    return run.run(tiny.cell(), tiny.config(), tiny.mix(), tiny.spec(), seed,
                   6.0, False, tiny.DEVICE, t_start=time.perf_counter(),
                   on_built=on_built)


def test_sound_run_is_correct():
    res = one_run()
    assert res["correct"] is True
    assert res["checks"]["logit_gap"]["value"] <= \
        res["checks"]["logit_gap"]["limit"]
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", [state_unchanged, half_batch,
                                   token_altered])
def test_broken_step_is_not_correct(fault):
    res = one_run(installer(fault))
    assert res["correct"] is False, res["checks"]


def test_control_reads_three_times_the_program():
    """The control (the reference computed in float8, in the program's
    place) on the same window: its widest gap is at least three times
    the program's, as the cell's limit needs."""
    from harness import correct, local

    cell, cfg, mix = tiny.cell(), tiny.config(), tiny.mix()
    seed = 2 ** 31 + 91
    w = run.serve(cfg, mix, seed, 6.0, False)
    done = [(s.req.prompt, s.tokens) for s in w.streams
            if len(s.tokens) >= s.req.max_new]
    picked = correct.sample(done, seed, 6)
    got = correct.read_gaps(cfg, local.model_seed(seed), picked,
                            cfg["serving"]["max_len"], control=True)
    assert got["control_gap"] >= 3 * got["logit_gap"], got
