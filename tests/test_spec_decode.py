"""Differential oracle: paged + speculative decode vs contiguous greedy.

The exactness contract of `models.transformer.paged_decode_step`: each
row runs the ops of a single-token `decode_step` — the page-table
gather/scatter is pure data movement — so for ANY spec_k the
scheduler's emitted token sequences must be IDENTICAL (not just close)
to a contiguous single-token greedy decode loop.  The arithmetic is not
bit-identical: on XLA:CPU a matmul of one row (the batch-1 loop) and of
several (a decode step over several slots, a prefill chunk) can round
differently in the last bit, so the tokens match wherever no such
difference decides a near-tie of the two top logits.  Verified here for a
dense GQA family (starcoder2) and an MLA family (minicpm3), including
streams physically sharing prefix pages, park/resume interleavings,
spill/refill through the pager, and kill/restore.

The comparison target is a direct batch-1 `decode_step` loop — the
canonical greedy semantics.  (Note: `jax.vmap` over batch-1 decode_step
— the contiguous scheduler's step — produces different bf16 rounding
than direct `decode_step` for MLA near argmax ties; the paged step
matches the direct loop bit-for-bit on both families, which is the
stronger anchor.)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api.session import ResilienceSession
from repro.cluster.topology import VirtualCluster
from repro.configs import get_config
from repro.core.scr import Strategy
from repro.models.registry import get_model
from repro.serve.kvpage import KVPager
from repro.serve.prefix import PrefixCache
from repro.serve.scheduler import PagedServeScheduler
from repro.serve.spec import NGramProposer


@pytest.fixture(scope="module", params=["starcoder2-7b", "minicpm3-4b"],
                ids=["gqa", "mla"])
def arch(request):
    # this module recompiles many decode variants; shed the XLA state
    # accumulated by the rest of the suite first (long single-process
    # runs have segfaulted in CPU XLA under compile-cache churn)
    jax.clear_caches()
    cfg = get_config(request.param).reduced()
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0), cfg)
    return cfg, model, params


def greedy_reference(cfg, model, params, prompt, max_new, max_len):
    """Direct batch-1 decode_step loop: canonical contiguous greedy."""
    cache = model.init_cache(cfg, 1, max_len)
    toks = list(prompt)
    pos, out = 0, []
    while len(out) < max_new and pos < max_len:
        tok = toks[pos]
        logits, cache = model.decode_step(
            params, cache, jnp.asarray([tok], jnp.int32), jnp.int32(pos), cfg)
        pos += 1
        if pos >= len(prompt):
            nxt = int(np.asarray(logits.argmax(axis=-1))[0])
            toks.append(nxt)
            out.append(nxt)
    return out


def check_all(sched, sids, prompts, refs):
    for sid, prompt, want in zip(sids, prompts, refs):
        got = sched.output(sid)
        assert got == want, (
            f"stream {sid} (prompt {list(prompt)}): {got} != greedy {want}")


MAX_LEN, MAX_NEW, PT = 24, 6, 4


@pytest.mark.parametrize("spec_k", [0, 3])
def test_paged_decode_is_exactly_greedy(arch, spec_k):
    """Multi-stream paged decode (with and without speculation) emits
    token sequences identical to the contiguous greedy loop."""
    cfg, model, params = arch
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab_size, size=int(rng.integers(2, 10)))
               for _ in range(5)]
    refs = [greedy_reference(cfg, model, params, list(p), MAX_NEW, MAX_LEN)
            for p in prompts]
    sched = PagedServeScheduler(cfg, model, params, slots=2, max_len=MAX_LEN,
                                quantum=3, page_tokens=PT, spec_k=spec_k)
    sids = [sched.submit(p, max_new=MAX_NEW) for p in prompts]
    sched.run()
    check_all(sched, sids, prompts, refs)
    assert sched.stats["parked"] > 0, "oversubscription must exercise parking"
    # park/resume never moved KV bytes: pages stayed pool-resident
    assert sched.stats["kv_resume_bytes_moved"] == 0
    if spec_k:
        assert sched.stats["spec_proposed"] > 0
    assert sched.pool.used_pages() == 0, "finished streams must free pages"


def test_speculation_accepts_on_repetitive_prompts(arch):
    """Greedy loops are where n-gram proposals win: acceptance must be
    strictly positive AND the output still exactly greedy."""
    cfg, model, params = arch
    prompt = [7, 8, 9] * 3          # periodic: lookup proposals hit
    want = greedy_reference(cfg, model, params, prompt, 10, 32)
    sched = PagedServeScheduler(cfg, model, params, slots=1, max_len=32,
                                page_tokens=PT, spec_k=2)
    sid = sched.submit(prompt, max_new=10)
    steps = sched.run()
    assert sched.output(sid) == want
    assert sched.stats["spec_accepted"] > 0, "no proposal ever accepted"
    # accepted speculation means fewer steps than tokens emitted
    assert steps < len(want) + 2


def test_shared_prefix_pages_and_spec(arch):
    """Streams sharing a prompt prefix decode through the SAME physical
    pool pages — outputs must still match per-stream greedy exactly."""
    cfg, model, params = arch
    rng = np.random.default_rng(23)
    shared = list(rng.integers(0, cfg.vocab_size, size=9))
    prompts = [shared + list(rng.integers(0, cfg.vocab_size, size=int(n)))
               for n in rng.integers(1, 5, size=5)]
    refs = [greedy_reference(cfg, model, params, p, MAX_NEW, MAX_LEN)
            for p in prompts]
    pager = KVPager.for_capacity(fast_bytes=10**8, page_bytes=4096)
    prefix = PrefixCache.for_model(pager.stack, cfg, model, MAX_LEN,
                                   page_tokens=PT)
    sched = PagedServeScheduler(cfg, model, params, slots=3, max_len=MAX_LEN,
                                page_tokens=PT, spec_k=2, pager=pager,
                                prefix=prefix)
    sids = [sched.submit(p, max_new=MAX_NEW) for p in prompts]
    sched.run()
    check_all(sched, sids, prompts, refs)
    assert sched.stats["prefix_pool_shared"] > 0, \
        "later streams must reference the resident prefix pages"
    assert sched.stats["prefill_tokens_saved"] > 0
    # only the digest-bound prefix pages stay resident after finish
    assert sched.pool.used_pages() == len(sched.pool.resident_digests())
    sched.close()


def test_spill_refill_under_pool_pressure(arch):
    """A pool too small for all resident streams forces page-granular
    spill/refill through the pager — the ONLY path that may move KV
    bytes — and outputs still match greedy exactly."""
    cfg, model, params = arch
    rng = np.random.default_rng(31)
    prompts = [rng.integers(0, cfg.vocab_size, size=int(rng.integers(2, 8)))
               for _ in range(6)]
    refs = [greedy_reference(cfg, model, params, list(p), MAX_NEW, MAX_LEN)
            for p in prompts]
    pager = KVPager.for_capacity(fast_bytes=10**8, page_bytes=4096)
    pages_per_lane = MAX_LEN // PT
    sched = PagedServeScheduler(cfg, model, params, slots=2, max_len=MAX_LEN,
                                quantum=2, page_tokens=PT, spec_k=1,
                                pager=pager, pool_pages=3 * pages_per_lane)
    sids = [sched.submit(p, max_new=MAX_NEW) for p in prompts]
    sched.run()
    check_all(sched, sids, prompts, refs)
    assert sched.stats["spilled"] > 0 and sched.stats["refilled"] > 0
    assert sched.stats["kv_resume_bytes_moved"] > 0
    assert (sched.stats["kv_resume_bytes_moved"]
            == sched.pager.stats()["kv_resume_bytes_moved"])
    assert sched.pool.used_pages() == 0
    sched.close()


def test_kill_restore_is_byte_identical(arch, tmp_path):
    """Kill mid-decode with speculation live: the restored pool buffer is
    byte-identical, and the continuation equals the uninterrupted run."""
    cfg, model, params = arch
    rng = np.random.default_rng(43)
    shared = list(rng.integers(0, cfg.vocab_size, size=6))
    prompts = [shared + list(rng.integers(0, cfg.vocab_size, size=3))
               for _ in range(4)]
    cluster = VirtualCluster(4, 0, root=tmp_path)

    def build(session, pager, prefix):
        return PagedServeScheduler(
            cfg, model, params, slots=2, max_len=MAX_LEN, quantum=2,
            page_tokens=PT, spec_k=2, pager=pager, prefix=prefix,
            session=session)

    with ResilienceSession.for_cluster(cluster, strategy=Strategy.XOR,
                                       procs_per_node=2) as session:
        pager1 = KVPager.for_capacity(fast_bytes=10**8, page_bytes=4096)
        prefix1 = PrefixCache.for_model(pager1.stack, cfg, model, MAX_LEN,
                                        page_tokens=PT)
        s1 = build(session, pager1, prefix1)
        for p in prompts:
            s1.submit(p, max_new=MAX_NEW)
        for _ in range(4):
            s1.step()
        s1.save()
        snap_tokens = {sid: list(s.tokens) for sid, s in s1.streams.items()}
        pool_before = s1.pool.snapshot()
        s1.run()    # ground truth: the uninterrupted continuation
        truth = {sid: s1.output(sid) for sid in s1.streams}

        # "fresh process": everything rebuilt from the checkpoint alone
        pager2 = KVPager.for_capacity(fast_bytes=10**8, page_bytes=4096)
        prefix2 = PrefixCache.for_model(pager2.stack, cfg, model, MAX_LEN,
                                        page_tokens=PT)
        s2 = build(session, pager2, prefix2)
        s2.restore()
        assert {sid: list(s.tokens)
                for sid, s in s2.streams.items()} == snap_tokens
        pool_after = s2.pool.snapshot()
        for name in pool_before:
            assert np.array_equal(pool_before[name], pool_after[name]), \
                f"pool leaf {name} not byte-identical after restore"
        s2.run()
        for sid in truth:
            assert s2.output(sid) == truth[sid], f"stream {sid} diverged"
        s1.close()
        s2.close()


def test_engine_paged_spec_matches_contiguous_engine(arch):
    """The ServeEngine lockstep surface: paged+speculative rows equal
    the contiguous engine's rows position for position."""
    from repro.serve.engine import ServeEngine
    cfg, model, params = arch
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cfg.vocab_size, size=(2, 5)).astype(np.int32)
    ref = ServeEngine(cfg, model, params, batch=2, max_len=MAX_LEN)
    first_ref = np.asarray(ref.prefill(prompt))
    rows_ref = ref.decode(5)
    ref.close()
    eng = ServeEngine(cfg, model, params, batch=2, max_len=MAX_LEN,
                      paged=True, spec_k=2, page_tokens=PT)
    first = np.asarray(eng.prefill(prompt))
    rows = eng.decode(5)
    eng.close()
    np.testing.assert_array_equal(first, first_ref)
    assert len(rows) == len(rows_ref)
    for i, (a, b) in enumerate(zip(rows, rows_ref)):
        np.testing.assert_array_equal(a, b, err_msg=f"row {i}")


def test_ngram_proposer_is_deterministic_and_bounded():
    p = NGramProposer(max_n=3, window=64)
    hist = [1, 2, 3, 1, 2, 3, 1, 2]
    a = p.propose(hist, 4)
    assert a == p.propose(list(hist), 4)       # pure function of history
    assert len(a) == 4
    assert a[0] == 3                           # continues the loop
    assert p.propose([], 3) == [0, 0, 0]
    assert p.propose([5], 2) == [5, 5]         # pad by repetition
