"""Admission prefill through the layer-major paged step.

`PagedServeScheduler._paged_prefill` feeds a prompt suffix through
`paged_decode_step` in `PREFILL_CHUNK`-row calls.  Each chunk must leave
the pool exactly as the same tokens fed one at a time through T=1 steps
would, and emit the same argmax tokens row for row: the chunk only
widens the row axis of the per-layer ops.  The suffix here starts off
any chunk or page boundary, crosses pages, and ends in a partial chunk
whose pad rows either stay inside the lane or run past its last slot;
the pool starts as noise, so any stray write shows.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.registry import get_model
from repro.serve.pagepool import TRASH_PAGE
from repro.serve.scheduler import PREFILL_CHUNK, PagedServeScheduler

MAX_LEN, PT, T0 = 160, 16, 21


@pytest.fixture(scope="module", params=[
    ("starcoder2-7b", None), ("minicpm3-4b", None), ("starcoder2-7b", "int8")],
    ids=["gqa", "mla", "gqa-int8"])
def arch(request):
    name, codec = request.param
    cfg = get_config(name).reduced()
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0), cfg)
    return cfg, model, params, codec


def _scheduler(arch):
    cfg, model, params, codec = arch
    sched = PagedServeScheduler(cfg, model, params, slots=1,
                                max_len=MAX_LEN, page_tokens=PT,
                                kv_codec=codec)
    keys = jax.random.split(jax.random.PRNGKey(7), len(sched.pool.leaves))
    for k, name in zip(keys, sorted(sched.pool.leaves)):
        leaf = sched.pool.leaves[name]
        if jnp.issubdtype(leaf.dtype, jnp.integer):
            noise = jax.random.randint(k, leaf.shape, -127, 128)
        else:
            noise = jax.random.normal(k, leaf.shape)
        sched.pool.leaves[name] = noise.astype(leaf.dtype)
    return sched


@pytest.mark.parametrize("t1", [154, MAX_LEN], ids=["pad-in-lane",
                                                    "pad-past-lane"])
def test_chunked_prefill_equals_token_steps(arch, t1):
    sched = _scheduler(arch)
    table = sched.pool.alloc(sched.pool.pages_per_lane)
    tokens = [int(t) for t in np.random.default_rng(3).integers(
        0, arch[0].vocab_size, size=MAX_LEN)]
    start = dict(sched.pool.leaves)

    # reference: the same tokens one at a time through T=1 steps over two
    # lanes, the second idle on the trash page, as a decode step over two
    # slots runs (XLA:CPU fuses a one-row matmul into its consumer as an
    # element-by-element loop and runs a matmul of two or more rows as a
    # library GEMM, so a one-lane reference could differ in the last bit)
    tables = jnp.asarray(np.stack([
        np.asarray(table, np.int32),
        np.full(len(table), TRASH_PAGE, np.int32)]))
    ref_pool, ref_out = dict(start), []
    for i in range(T0, t1):
        out, ref_pool = sched._paged_fn(
            sched.params, ref_pool, tables, jnp.asarray([i, 0], np.int32),
            jnp.asarray([[tokens[i]], [-1]], np.int32))
        ref_out.append(int(np.asarray(out)[0, 0]))

    # the admission path, its calls recorded
    inner, calls = sched._paged_fn, []

    def recording(p, pools, tables, pos, toks):
        out, pools = inner(p, pools, tables, pos, toks)
        calls.append((int(pos[0]), np.asarray(toks)[0], np.asarray(out)[0]))
        return out, pools

    sched._paged_fn = recording
    sched._paged_prefill(table, tokens, T0, t1)

    n = t1 - T0
    n_calls = -(-n // PREFILL_CHUNK)
    assert [c[0] for c in calls] == [T0 + j * PREFILL_CHUNK
                                     for j in range(n_calls)]
    got = []
    for _, toks, out in calls:
        assert toks.shape == (PREFILL_CHUNK,)
        m = min(PREFILL_CHUNK, n - len(got))
        assert (toks[:m] >= 0).all() and (toks[m:] == -1).all()
        got.extend(int(v) for v in out[:m])
    assert got == ref_out, "chunk argmax differs from token-at-a-time"

    assert sched.stats["prefill_calls"] == n_calls
    assert sched.stats["prefill_tokens"] == n
    assert sched.stats["prefill_pad_tokens"] == n_calls * PREFILL_CHUNK - n

    # the cells each pool wrote: exactly the chunk's real positions, in
    # the lane's pages, and every pad row in the trash page
    cfg, codec = arch[0], arch[3]
    written = np.zeros((cfg.n_layers,) + start[next(iter(start))].shape[1:3],
                       bool)
    for i in range(T0, t1):
        written[:, table[i // PT], i % PT] = True
    keep = np.arange(written.shape[1]) != TRASH_PAGE
    for name, leaf in sched.pool.leaves.items():
        now, ref, was = (np.asarray(a, np.float32)
                         for a in (leaf, ref_pool[name], start[name]))
        axes = tuple(range(3, now.ndim))
        for got_pool in (now, ref):
            changed = (got_pool != was).any(axis=axes)
            np.testing.assert_array_equal(changed[:, keep], written[:, keep],
                                          err_msg=name)
        if codec is None:
            # plain pools: byte for byte the token-at-a-time pool (the
            # exactness contract leaves quantized pools out)
            np.testing.assert_array_equal(now[:, keep], ref[:, keep],
                                          err_msg=name)
    trash_hit = any(not np.array_equal(np.asarray(leaf)[:, TRASH_PAGE],
                                       np.asarray(start[name])[:, TRASH_PAGE])
                    for name, leaf in sched.pool.leaves.items())
    assert trash_hit, "pad rows must land in the trash page"
    sched.close()
