#!/usr/bin/env python3
"""Smoke run of the serving path on a TPU v5e: the quickest proof that
the system still starts on the chip.

    python3 chip_smoke.py            # one chip
    python3 chip_smoke.py --fleet    # four chips, one worker per chip

One chip: phi3-mini-3.8b at its published widths (32 layers, d_model
3072, 32 heads of 96, d_ff 8192, vocab 32064), random weights from a
seed, held in bfloat16, served through ``Serve.local`` ->
``PagedServeScheduler`` -> the jitted ``paged_decode_step``.  More
requests than slots, sharing a system prompt or not, run to completion;
a checkpoint is taken mid-decode through a ``ResilienceSession``; the
stack is closed and its weights freed; a fresh stack restores the
checkpoint and must finish token-identical to the uninterrupted run.
One ``model.forward`` over a prompt must give finite logits whose
greedy token is the paged path's first token (or ties with it to within
a few bf16 ulps: the two paths round differently).

``--fleet`` runs only the fleet phase: ``Serve.fleet`` with four
one-chip workers behind a frontend that never touches JAX, a no-kill
run, then the same requests with one worker SIGKILLed mid-decode; every
stream must come out token-identical.

Sizing (one v5e chip, 16 GB HBM; 15.75 GB usable): the weights are
7.64 GB in bfloat16 (float32 would be 15.3 GB and cannot fit).  The
page pool holds POOL_PAGES pages of PAGE_TOKENS tokens plus a trash
page: 2 x 32 layers x 129 x 16 x 32 x 96 x 2 B = 0.81 GB, which the
chip lays out with the 96-wide head padded to 128 lanes: 1.08 GB.  The
step donates nothing, so each call also writes a second pool (1.08 GB):
by ``memory_analysis()`` of the v5e compile, 9.81 GB for the T=1 step
and for the 128-token prefill chunk alike (the token-at-a-time T=8
chunk it replaced kept 1.08 GB more).  On the chip the process peaked
at 13.07 GB in use with the T=8 chunk.

Earlier lines print smoke facts, not benchmark metrics.  The last line
is one JSON object: ``{"ok": true, "device": {"platform", "kind",
"count"}}``.  Without a TPU the script exits non-zero and prints no
result.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Dict, List

ROOT = Path(__file__).resolve().parent

ARCH = "phi3-mini-3.8b"
PARAM_DTYPE = "bfloat16"
SEED = 0
SLOTS = 4
MAX_LEN = 256
PAGE_TOKENS = 16
POOL_PAGES = 128          # 2 x SLOTS full lanes: parked streams stay resident
QUANTUM = 3               # steps before a stream yields its slot
N_REQUESTS = 6            # more streams than slots: admission and parking run
MAX_NEW = 24
SYSTEM_TOKENS = 32        # the shared prompt: two full pages
SAVE_AFTER = 8            # scheduler steps before the mid-decode checkpoint
BF16_ULPS = 4             # forward/paged first-token near-tie allowance
FLEET_WORKERS = 4
FLEET_REQUESTS = 8
FLEET_MAX_NEW = 48
FLEET_CKPT_EVERY = 4


def say(msg: str) -> None:
    print(msg, flush=True)


def published_arch():
    """phi3-mini-3.8b as published, with its weights held in bfloat16."""
    from repro.configs import get_config

    return dataclasses.replace(get_config(ARCH), param_dtype=PARAM_DTYPE)


def prompts(vocab: int, n: int, seed: int) -> List[List[int]]:
    """``n - 1`` prompts sharing a system prompt plus one that shares
    nothing, each with a distinct suffix."""
    import numpy as np

    rng = np.random.default_rng(seed)
    system = rng.integers(0, vocab, size=SYSTEM_TOKENS).tolist()
    out = [system + rng.integers(0, vocab, size=int(rng.integers(5, 13))
                                 ).tolist() for _ in range(n - 1)]
    out.append(rng.integers(0, vocab, size=SYSTEM_TOKENS + 7).tolist())
    return out


class CompileClock:
    """Sums the seconds JAX spends compiling, reads of the persistent
    compile cache included (that is the cache's whole saving)."""

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


def serve_config(arch, **kw):
    from repro.serve import ServeConfig

    return ServeConfig(arch=arch, full_size=True, seed=SEED, slots=SLOTS,
                       max_len=MAX_LEN, page_tokens=PAGE_TOKENS,
                       pool_pages=POOL_PAGES, quantum=QUANTUM, **kw)


def run_smoke(arch, workdir: Path, n_requests: int = N_REQUESTS,
              max_new: int = MAX_NEW) -> Dict[str, Any]:
    """The one-chip phases on ``arch`` exactly as given; raises on any
    failed check and returns the facts it printed."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.api import ResilienceSession
    from repro.core.scr import Strategy
    from repro.serve import Serve

    def peak():
        stats = jax.devices()[0].memory_stats() or {}
        return stats.get("peak_bytes_in_use", "n/a")

    cfg = serve_config(arch)
    say(f"arch {arch.name}: {arch.n_layers} layers, d_model {arch.d_model}, "
        f"{arch.n_heads} heads of {arch.resolved_head_dim} "
        f"(kv {arch.n_kv_heads}), d_ff {arch.d_ff}, vocab {arch.vocab_size}, "
        f"params {arch.param_dtype}, compute {arch.compute_dtype}")
    session = ResilienceSession.for_shared_tier(
        workdir, strategy=Strategy.SINGLE, domain="smoke")
    try:
        # phase 1: build, serve past the checkpoint, run to the end
        t0 = time.perf_counter()
        with CompileClock() as clock_a:
            srv = Serve.local(cfg, session=session)
            jax.block_until_ready(srv.params)
            build_s = time.perf_counter() - t0
            leaves = srv.scheduler.pool.leaves
            pool_bytes = sum(int(l.nbytes) for l in leaves.values())
            del leaves
            say(f"built in {build_s:.1f} s; pool {POOL_PAGES} pages of "
                f"{PAGE_TOKENS} tokens (+1 trash), {pool_bytes} bytes; "
                f"peak device bytes {peak()}")
            reqs = prompts(arch.vocab_size, n_requests, SEED)
            sids = [srv.submit(p, max_new=max_new) for p in reqs]
            for _ in range(SAVE_AFTER):
                srv.step()
            mid = [len(srv.output(s)) for s in sids]
            if not any(0 < n < max_new for n in mid):
                raise RuntimeError(f"checkpoint is not mid-decode: {mid}")
            srv.save()
            saved_step = srv.scheduler.step_count
            srv.run()
            ref = {s: srv.output(s) for s in sids}
        short = {s: len(t) for s, t in ref.items() if len(t) != max_new}
        if short:
            raise RuntimeError(f"requests ended short of {max_new}: {short}")
        stats = srv.stats
        say(f"served {len(sids)} requests on {SLOTS} slots: "
            f"{sum(map(len, ref.values()))} tokens, {stats['steps']} steps, "
            f"{stats['parked']} parks, {stats['resumed']} resumes, "
            f"{stats['prefix_hits']} prefix hits, "
            f"{stats['prefill_tokens_saved']} prefill tokens from cache; "
            f"checkpoint at step {saved_step} with {mid} tokens out; "
            f"peak device bytes {peak()}")

        # phase 2: one plain forward over a prompt
        with CompileClock() as clock_f:
            fwd = jax.jit(lambda p, t: srv.model.forward(
                p, {"tokens": t}, arch, remat=False)[0])
            logits = fwd(srv.params, jnp.asarray([reqs[0]], jnp.int32))
            last = np.asarray(logits[0, -1].astype(jnp.float32))
        if not bool(jnp.isfinite(logits).all()):
            raise RuntimeError("forward logits are not finite")
        fwd_tok, paged_tok = int(last.argmax()), ref[sids[0]][0]
        # the two paths round differently (chunked prefill attention
        # against token-at-a-time paged decode, bf16 logits), so they
        # agree when the paged token is the forward's argmax or within
        # BF16_ULPS bf16 ulps of it: a near-tie, not a wrong token
        gap = float(last[fwd_tok] - last[paged_tok])
        ulp = 2.0 ** (np.floor(np.log2(max(abs(last[fwd_tok]), 1e-30))) - 7)
        say(f"forward logits {tuple(logits.shape)} finite; greedy token "
            f"{fwd_tok}, paged first token {paged_tok}, forward logit gap "
            f"{gap:.4f} ({gap / ulp:.1f} bf16 ulps); peak device bytes "
            f"{peak()}")
        if gap > BF16_ULPS * ulp:
            raise RuntimeError("forward and paged first tokens differ")

        # phase 3: free the first stack, restore into a fresh one
        srv.close()
        del srv, fwd, logits
        gc.collect()
        live = sum(int(a.nbytes) for a in jax.live_arrays())
        say(f"first stack closed; {live} bytes of arrays still live")
        t0 = time.perf_counter()
        with CompileClock() as clock_b:
            srv = Serve.local(cfg, session=session)
            got = srv.restore()
            srv.run()
            out = {s: srv.output(s) for s in sids}
        restore_s = time.perf_counter() - t0
        srv.close()
        del srv
        identical = out == ref
        say(f"restored step {got} into a fresh stack and ran on in "
            f"{restore_s:.1f} s; outputs token-identical: {identical}")
        if not identical:
            raise RuntimeError("restored outputs differ from the "
                               "uninterrupted run")
    finally:
        session.close()

    say(f"compile seconds: first stack {clock_a.seconds:.1f} "
        f"({clock_a.programs} programs), forward {clock_f.seconds:.1f}, "
        f"restored stack {clock_b.seconds:.1f} ({clock_b.programs} programs)")
    say(f"peak device bytes in use: {peak()}")
    return {"pool_pages": POOL_PAGES, "pool_bytes": pool_bytes,
            "tokens": sum(map(len, ref.values())), "identical": identical,
            "compile_s": (clock_a.seconds, clock_b.seconds),
            "first_token": (fwd_tok, paged_tok)}


def run_fleet(arch, workdir: Path, workers: int = FLEET_WORKERS,
              n_requests: int = FLEET_REQUESTS,
              max_new: int = FLEET_MAX_NEW,
              timeout: float = 300.0) -> Dict[str, Any]:
    """The fleet phase.  This process never touches JAX: each worker is
    a spawned process holding one chip."""
    from repro.serve import Serve
    from repro.serve.fleet import PrefixBoard
    from repro.serve.fleet.board import record_kind

    cfg = serve_config(arch, fast_bytes=1 << 30, shared_capacity=16 << 30,
                       ckpt_every=FLEET_CKPT_EVERY)
    reqs = prompts(arch.vocab_size, n_requests, SEED + 1)
    t0 = time.perf_counter()
    fe = Serve.fleet(cfg, workers=workers, shared_root=str(workdir),
                     ready_timeout=timeout)
    try:
        devices = [w.device for w in fe.workers]
        say(f"fleet of {workers} up in {time.perf_counter() - t0:.1f} s: "
            + "; ".join(f"w{i} {d['platform']} {d['kind']} chip "
                        f"{d['visible_chips']} at {d['coords']} "
                        f"({d['count']} visible)"
                        for i, d in enumerate(devices)))
        archs = {w.arch for w in fe.workers}
        if archs != {arch}:
            raise RuntimeError(f"workers serve {archs}, not {arch}")

        # no-kill run: the oracle
        t0 = time.perf_counter()
        rids = [fe.submit(p, max_new=max_new) for p in reqs]
        fe.wait(rids, timeout=timeout)
        ref = [fe.result(r) for r in rids]
        say(f"no-kill run: {sum(map(len, ref))} tokens in "
            f"{time.perf_counter() - t0:.1f} s")

        # the same requests again; kill worker 0 once it has checkpointed
        # an epoch covering its streams and each is mid-decode
        board = PrefixBoard(Path(workdir))
        board.poll()
        wall = time.time()
        victim = 0
        name = fe.workers[victim].spec.name
        rids = [fe.submit(p, max_new=max_new) for p in reqs]
        victims: List[int] = []
        killed = epoch = False
        deadline = time.monotonic() + timeout
        while not all(len(fe.progress(r)) >= max_new for r in rids):
            if time.monotonic() > deadline:
                raise TimeoutError("fleet kill run did not finish")
            fe.pump()
            if not killed:
                if not victims:
                    victims = [r for r in rids
                               if fe.assignment(r) == victim]
                epoch = epoch or any(
                    record_kind(rec) == "epoch" and rec.get("worker") == name
                    and rec.get("t", 0.0) >= wall for rec in board.poll())
                if victims and epoch and all(
                        0 < len(fe.progress(r)) < max_new for r in victims):
                    fe.workers[victim].kill()
                    killed = True
            time.sleep(0.002)
        fe.wait(rids, timeout=timeout)
        out = [fe.result(r) for r in rids]
        stats = dict(fe.stats)
        if not killed or not victims:
            raise RuntimeError("the kill never fired")
        identical = out == ref
        say(f"kill run: worker {name} SIGKILLed with {len(victims)} "
            f"streams mid-decode; workers_failed "
            f"{stats['workers_failed']}, streams_migrated "
            f"{stats['streams_migrated']}; outputs token-identical to the "
            f"no-kill run: {identical}")
        if not identical or stats["workers_failed"] != 1:
            raise RuntimeError("fleet recovery check failed")
    finally:
        fe.stop()
    return {"devices": devices, "identical": identical,
            "migrated": stats["streams_migrated"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--fleet", action="store_true",
                    help=f"run only the fleet phase: {FLEET_WORKERS} "
                         "workers, one per chip")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.chip import enable_compile_cache

    cache = enable_compile_cache()
    entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    say(f"compile cache {cache} ({entries} entries at start)")
    workdir = Path(tempfile.mkdtemp(prefix="chip_smoke_"))
    try:
        if args.fleet:
            platforms = os.environ.get("JAX_PLATFORMS", "tpu")
            if "tpu" not in platforms:
                print(f"no TPU: JAX_PLATFORMS={platforms}", file=sys.stderr)
                return 1
            facts = run_fleet(published_arch(), workdir)
            devices = facts["devices"]
            chips = {d["visible_chips"] for d in devices}
            if (any(d["platform"] != "tpu" or d["count"] != 1
                    for d in devices) or len(chips) != len(devices)):
                print(f"workers are not on distinct chips: {devices}",
                      file=sys.stderr)
                return 1
            device = {"platform": devices[0]["platform"],
                      "kind": devices[0]["kind"], "count": len(chips)}
        else:
            import jax

            devs = jax.devices()
            if devs[0].platform != "tpu":
                print(f"no TPU: JAX found {devs[0].platform}",
                      file=sys.stderr)
                return 1
            run_smoke(published_arch(), workdir)
            device = {"platform": devs[0].platform,
                      "kind": devs[0].device_kind, "count": len(devs)}
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
