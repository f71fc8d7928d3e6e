"""Multi-request serving example: continuous batching + tiered KV paging
+ shared-prefix page cache, built through the unified serving API.

Submits more decode streams than there are decode slots — all opening
with the same "system prompt" — and lets the ServeScheduler round-robin
them: the first stream's prompt populates the PrefixCache, every later
stream fetches those shared KV pages instead of recomputing them
(prefill tokens saved), parked streams page their caches through the
TierStack as content-addressed page tables (admission control +
hit-rate promotion decide the tier), the full multi-stream state —
dedup'd page pool and prefix trie included — is checkpointed through an
SCR-style session mid-decode, the scheduler AND a node are killed, and
a fresh scheduler restores everything and finishes byte-identically.

All construction goes through ``ServeConfig`` + ``Serve.local`` /
``Serve.fleet`` (repro/serve/api.py) — one declarative config instead
of hand-wiring pager/prefix/scheduler kwargs.

  PYTHONPATH=src python examples/serve.py [--arch minicpm3-4b] [--steps 8]

With ``--workers N`` (N > 1) the same workload instead runs as a
serving *fleet*: N spawned worker processes over one shared cache
domain, an admission front-end with tenant quotas routing the streams,
and the shared system prompt computed once fleet-wide — workers that
never saw it pull its KV pages out of the shared tier:

  PYTHONPATH=src python examples/serve.py --workers 2
"""

import argparse
import tempfile
from pathlib import Path

import numpy as np

from repro.api import ResilienceSession
from repro.cluster.topology import VirtualCluster
from repro.core.scr import Strategy
from repro.launch.chip import enable_compile_cache
from repro.serve import Serve, ServeConfig


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minicpm3-4b")
    ap.add_argument("--steps", type=int, default=8,
                    help="decode steps before the mid-stream checkpoint/kill")
    ap.add_argument("--streams", type=int, default=6)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--max-new", type=int, default=6)
    ap.add_argument("--workers", type=int, default=1,
                    help="run as a fleet of N worker processes over one "
                         "shared cache domain (N > 1)")
    args = ap.parse_args()

    if args.workers > 1:
        fleet_main(args)
        return

    # the whole stack from one config: contiguous lanes here (the paged
    # pool path is the fleet's default), a fast tier that holds only a
    # few lane caches so oversubscription forces parked streams down
    # the hierarchy (fast_bytes=None auto-sizes to slots + 1 lanes)
    cfg = ServeConfig(arch=args.arch, paged=False, slots=args.slots,
                      max_len=32, page_tokens=4, quantum=3)

    rng = np.random.default_rng(7)
    srv = Serve.local(cfg)
    vocab = srv.arch.vocab_size
    system_prompt = rng.integers(0, vocab, size=9).tolist()
    prompts = [system_prompt
               + rng.integers(0, vocab,
                              size=int(rng.integers(3, 8))).tolist()
               for _ in range(args.streams)]

    # reference: the same workload decoded with no interruption
    for p in prompts:
        srv.submit(p, max_new=args.max_new)
    srv.run()
    ref = {sid: srv.output(sid) for sid in srv.scheduler.streams}
    ref_stats = dict(srv.stats)
    srv.close()

    root = Path(tempfile.mkdtemp(prefix="deeper_serve_"))
    cluster = VirtualCluster(4, 4, root=root)
    with ResilienceSession.for_cluster(cluster, strategy=Strategy.XOR,
                                       procs_per_node=2) as session:
        srv = Serve.local(cfg, session=session)
        for p in prompts:
            srv.submit(p, max_new=args.max_new)
        srv.run(max_steps=args.steps)       # decode partway...
        srv.save()                          # ...one transaction saves it all
        parked = len(srv.pager.parked_sids())
        srv.close()                         # the "kill": all state gone

        # a node dies too; XOR reconstruction covers the lost fragments
        cluster.fail(1)
        cluster.recover(1)
        session.invalidate_node(1)

        srv2 = Serve.local(cfg, session=session)   # fresh process stand-in
        srv2.restore()                      # stream set comes from the ckpt
        srv2.run()
        out = {sid: srv2.output(sid) for sid in srv2.scheduler.streams}
        srv2.close()

    assert out == ref, "post-restore decode diverged"
    total = sum(len(v) for v in out.values())
    print(f"decoded {total} tokens across {args.streams} streams on "
          f"{srv2.arch.name} ({args.slots} slots, quantum 3): "
          f"{ref_stats['parked']} parks, {ref_stats['resumed']} resumes, "
          f"max {ref_stats['max_resident']} resident")
    print(f"shared system prompt: {ref_stats['prefix_hits']} prefix hits, "
          f"{ref_stats['prefill_tokens_saved']} prefill tokens never "
          f"recomputed ({ref_stats['prefill_tokens']} computed)")
    print(f"OK: killed mid-decode with {parked} streams parked + a node "
          f"loss; restored scheduler finished every stream byte-identically.")
    cluster.teardown()


def fleet_main(args):
    """--workers N: the same shared-prompt workload through the fleet
    (serve/fleet): spawned workers over one SharedTier domain, admission
    front-end with tenant quotas, cross-process prefix reuse — built by
    ``Serve.fleet`` from the same config shape as the local path."""
    from repro.serve.fleet import TenantQuota

    cfg = ServeConfig(arch=args.arch, slots=args.slots, max_len=32,
                      page_tokens=4, quantum=3)
    rng = np.random.default_rng(7)
    # vocab size differs per arch; workers build the config themselves,
    # so sample from a safe floor every arch clears
    system_prompt = rng.integers(0, 1000, size=9).tolist()
    prompts = [system_prompt
               + rng.integers(0, 1000, size=int(rng.integers(3, 8))).tolist()
               for _ in range(args.streams)]

    with Serve.fleet(cfg, workers=args.workers,
                     quotas={"bulk": TenantQuota(2)}) as fe:
        rids = [fe.submit(p, max_new=args.max_new,
                          tenant="bulk" if i % 2 else "latency",
                          prio="batch" if i % 2 else "interactive")
                for i, p in enumerate(prompts)]
        fe.wait(rids, timeout=600)
        outs = {r: fe.result(r) for r in rids}
        stats = fe.worker_stats()

    total = sum(len(v) for v in outs.values())
    assert all(len(v) == args.max_new for v in outs.values())
    saved = sum(s["scheduler"]["prefill_tokens_saved"] for s in stats)
    computed = sum(s["scheduler"]["prefill_tokens"] for s in stats)
    adopted = sum(s["prefix"]["nodes_adopted"] for s in stats)
    shared_hits = sum(s["tier"].get("hits_shared", 0) for s in stats)
    print(f"fleet of {args.workers} workers decoded {total} tokens across "
          f"{args.streams} streams ({fe.stats['throttle_events']} throttle "
          f"events on the quota'd tenant)")
    print(f"shared system prompt fleet-wide: {saved} prefill tokens never "
          f"recomputed ({computed} computed), {adopted} trie nodes adopted "
          f"from peers, {shared_hits} shared-tier page hits")
    print("OK: cross-process prefix sharing through one cache domain.")


if __name__ == "__main__":
    main()
