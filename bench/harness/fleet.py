"""The ``fleet`` driver: a cell served by ``Serve.fleet``, one worker
process a chip behind the program's ``FleetFrontend``, under an open loop.

The configuration's ``serving`` block gives ``workers`` and what each
worker runs (``slots``, ``max_len``, ``page_tokens``, ``pool_pages``,
``prefix``, ``ckpt_every``; no round-robin preemption, as a chat
deployment admits in arrival order), the frontend's ``hb_timeout_s`` and
the one tenant's ``tenant_inflight``, and the worker's host tier
(``fast_bytes``) and the fleet's shared cache domain
(``shared_capacity``).  Worker ``i`` holds chip ``i``; this process never
touches JAX, and each worker reports its device (``fleet_worker.py``):
where the cell asks for ``chips``, each looks for a TPU before it builds
anything, and the window's device counts the distinct chips they hold.

Set-up: the fleet is launched and every worker warmed, in rounds of the
mix's own sizes that each run to their end, on every shape the window
uses (``warmup``: at least two requests a worker a round, and enough new
tokens for an epoch checkpoint).  The window is an open loop: requests
are submitted at their arrival times (``traffic.Arrivals``) whatever is
still running, and the frontend is pumped between arrivals; a token's
time is when ``pump()`` first shows it, as a client of the router sees
it.  A mix with ``kill`` SIGKILLs worker ``kill.worker`` once the window
has delivered ``kill.after_tokens`` tokens; the frontend's own
``recover_worker`` span and ``migrate`` events are read from its tracer,
and each migrated stream is marked.  The workers' merged statistics
(``fleet_stats()``) and each worker's instruments are read at both ends
of the window; every worker has exited when ``run_window`` returns.
``trace`` changes nothing here: no device trace is taken across the
workers, and no per-layer metric reads a fleet window yet.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import sys
import tempfile
import time
from multiprocessing.connection import wait as wait_conns
from typing import Dict, List, Optional, Tuple

from harness import fleet_worker
from harness.device import NoAccelerator
from harness.local import Stream, Window, model_seed
from harness.traffic import Arrivals, Generator, warmup_rounds

# the frontend's tracer keeps this many records (submit events included)
TRACE_RECORDS = 1 << 17
# how long a warm-up round, and the launch, may take
WARM_TIMEOUT_S, READY_TIMEOUT_S = 600.0, 1200.0


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class FleetWindow(Window):
    lost: Optional[int] = None            # the worker the mix kills
    kill_t: Optional[float] = None        # when it was SIGKILLed
    kill_tokens: int = 0                  # tokens delivered by then
    recover: Optional[Tuple[float, float]] = None   # recover_worker
    # (stream index, time, tokens replayed) of each migrate event
    migrate: List[Tuple[int, float, int]] = dataclasses.field(
        default_factory=list)
    stats: Dict = dataclasses.field(default_factory=dict)
    # per worker: name, peak bytes, compiles in the window, bytes
    # written, whether it was read at the window's end
    workers: List[Dict] = dataclasses.field(default_factory=list)
    late_s: float = 0.0                   # the generator's latest submit


def serve_config(cfg: Dict, seed: int):
    from repro.serve import ServeConfig

    from harness.local import arch_of

    s = cfg["serving"]
    return ServeConfig(
        arch=arch_of(cfg), full_size=True, seed=model_seed(seed),
        slots=s["slots"], max_len=s["max_len"],
        page_tokens=s["page_tokens"], pool_pages=s["pool_pages"],
        quantum=0, prefix=s["prefix"], fast_bytes=s["fast_bytes"],
        shared_capacity=s["shared_capacity"], ckpt_every=s["ckpt_every"],
        hb_timeout_s=s["hb_timeout_s"])


@contextlib.contextmanager
def launched(cfg: Dict, seed: int, chips: Optional[int] = None):
    """The fleet of ``cfg`` with weights from ``seed``, stopped and its
    shared domain removed on leaving; with ``chips``, each worker exits
    before it builds anything where it finds no TPU."""
    import repro.serve.fleet.worker as W
    from repro.obs.trace import Tracer
    from repro.serve import Serve
    from repro.serve.fleet import TenantQuota

    s = cfg["serving"]
    saved = W.worker_main, os.environ.get(fleet_worker.PLATFORM_ENV)
    if chips is not None:
        os.environ[fleet_worker.PLATFORM_ENV] = "tpu"
    W.worker_main = fleet_worker.main
    fe = None
    with tempfile.TemporaryDirectory(prefix="bench_fleet_") as root:
        try:
            fe = Serve.fleet(
                serve_config(cfg, seed), workers=s["workers"],
                shared_root=root, ready_timeout=READY_TIMEOUT_S,
                default_quota=TenantQuota(s["tenant_inflight"]),
                tracer=Tracer(capacity=TRACE_RECORDS, process="frontend"))
        except RuntimeError as e:
            if "no_accelerator" in str(e):
                raise NoAccelerator(f"a worker found no TPU: {e}") from None
            raise
        finally:
            W.worker_main = saved[0]
            if saved[1] is None:
                os.environ.pop(fleet_worker.PLATFORM_ENV, None)
        try:
            yield fe
        finally:
            fe.stop()
            for w in fe.workers:
                if w.proc.is_alive():
                    w.proc.kill()
                w.proc.join(10)


def device_of(fe, chips: Optional[int] = None) -> Dict:
    """The fleet's device as its workers report it; raises
    :class:`NoAccelerator` where they hold fewer than ``chips`` distinct
    TPU chips (``None``: the tests on the CPU, nothing checked)."""
    devs = [w.device for w in fe.workers]
    held = {d.get("visible_chips") for d in devs}
    if chips is not None:
        if any(d["platform"] != "tpu" for d in devs):
            raise NoAccelerator(f"workers on {[d['platform'] for d in devs]}")
        if len(held) < chips:
            raise NoAccelerator(f"the cell asks for {chips} chips, the "
                                f"workers hold {sorted(held)}")
    return {"platform": devs[0]["platform"], "kind": devs[0]["kind"],
            "count": len(held)}


def warm(fe, rounds) -> None:
    """Run each round to its end; every worker has to get some of each."""
    for i, reqs in enumerate(rounds):
        rids = [fe.submit(r.prompt, r.max_new) for r in reqs]
        fe.pump()
        held = [fe.assignment(r) for r in rids]
        fe.wait(rids, timeout=WARM_TIMEOUT_S)
        idle = set(range(len(fe.workers))) - set(held)
        if idle:
            raise RuntimeError(f"warm-up round {i} left workers {idle} idle")


def worker_extras(fe, skip=()) -> List[Optional[Dict]]:
    out = []
    for i, w in enumerate(fe.workers):
        out.append(None if i in skip else w.stats()["bench"])
    return out


class OpenLoop:
    """The measured window over one fleet."""

    def __init__(self, fe, mix: Dict, seed: int, vocab: int,
                 seconds: float):
        self.fe, self.seconds = fe, seconds
        self.gen = Generator(mix, seed, vocab)
        self.arrivals = Arrivals(mix, seed)
        kill = mix.get("kill")
        self.lost = None if kill is None else int(kill["worker"])
        self.kill_after = None if kill is None else int(kill["after_tokens"])
        self.kill_t: Optional[float] = None
        self.kill_tokens = 0
        self.streams: List[Stream] = []
        self.live: Dict[int, Stream] = {}
        self.delivered = 0
        self.late = 0.0

    def run(self) -> float:
        fe = self.fe
        t0 = time.perf_counter()
        end, due = t0 + self.seconds, t0
        while True:
            now = time.perf_counter()
            if now >= end:
                return t0
            while due <= now:
                self.late = max(self.late, now - due)
                req = self.gen.next()
                rid = fe.submit(req.prompt, req.max_new)
                st = Stream(req, rid, due)
                self.streams.append(st)
                self.live[rid] = st
                due += self.arrivals.next()
            try:
                fe.pump()
            except Exception as e:
                now = time.perf_counter()
                since = ("" if self.kill_t is None else
                         f", {1e3 * (now - self.kill_t):.1f} ms after the "
                         f"SIGKILL of w{self.lost}")
                say(f"FleetFrontend.pump() raised {type(e).__name__}: {e} "
                    f"{now - t0:.3f} s into the window{since}; frontend "
                    f"stats {dict(fe.stats)}")
                raise
            self._observe(time.perf_counter())
            if (self.kill_t is None and self.lost is not None
                    and self.delivered >= self.kill_after):
                self.kill_tokens = self.delivered
                self.kill_t = time.perf_counter()
                fe.workers[self.lost].kill()
            conns = [w.conn for i, w in enumerate(fe.workers)
                     if i not in fe._dead
                     and not (self.kill_t is not None and i == self.lost)]
            wait_conns(conns, max(0.0, min(due, end)
                                  - time.perf_counter()))

    def _observe(self, now: float) -> None:
        """Stamp every token the frontend shows that was not seen yet."""
        reqs = self.fe._requests
        for rid, st in list(self.live.items()):
            req = reqs[rid]
            toks = req.tokens
            n = len(toks)
            if n > len(st.tokens):
                self.delivered += n - len(st.tokens)
                st.times.extend([now] * (n - len(st.tokens)))
                st.tokens.extend(toks[len(st.tokens):n])
            if req.done:
                del self.live[rid]


def merged_counter(snap: Dict, dotted: str) -> float:
    v = snap.get("counters", {})
    for part in dotted.split("."):
        v = v.get(part, {}) if isinstance(v, dict) else {}
    return float(v) if not isinstance(v, dict) else 0.0


def run_window(cfg: Dict, mix: Dict, seed: int, seconds: float,
               trace: bool, chips: Optional[int] = None,
               on_built=None) -> FleetWindow:
    """Launch, check the device, warm, measure, read, and stop the fleet.
    ``on_built`` (tests only) is called with the frontend before
    warm-up."""
    t = time.perf_counter()
    with launched(cfg, seed, chips) as fe:
        device = device_of(fe, chips)
        built = time.perf_counter() - t
        if on_built is not None:
            on_built(fe)
        warm(fe, warmup_rounds(mix, seed, cfg["vocab_size"]))
        start = worker_extras(fe)
        stats0 = fe.fleet_stats()["merged"]
        say(f"set-up: fleet of {len(fe.workers)} up in {built:.1f} s "
            f"({device['platform']} {device['kind']}, chips "
            f"{[w.device.get('visible_chips') for w in fe.workers]}), "
            f"warm-up in {time.perf_counter() - t - built:.1f} s; programs "
            f"compiled or loaded a worker: {[e['compiles'] for e in start]}")
        loop = OpenLoop(fe, mix, seed, cfg["vocab_size"], seconds)
        t0 = loop.run()
        t1 = time.perf_counter()
        lost = {loop.lost} if loop.kill_t is not None else set()
        end = worker_extras(fe, skip=lost | set(fe._dead))
        stats1 = fe.fleet_stats()["merged"]
        recs = fe.tracer.records()
    index = {st.sid: i for i, st in enumerate(loop.streams)}
    w = FleetWindow(t0=t0, t1=t1, streams=loop.streams, steps=[],
                    prefills=[], compiles=0, device=device,
                    lost=loop.lost, kill_t=loop.kill_t,
                    kill_tokens=loop.kill_tokens, late_s=loop.late,
                    stats={"start": stats0, "end": stats1})
    for r in recs:
        if r["name"] == "recover_worker" and r["ts"] >= t0:
            w.recover = (r["ts"], r["dur"])
        elif r["name"] == "migrate" and r["tid"] in index:
            i = index[r["tid"]]
            w.migrate.append((i, r["ts"], int(r["args"]["replayed"])))
            loop.streams[i].migrated = True
    for i, (a, b) in enumerate(zip(start, end)):
        ww = {"name": f"w{i}", "read_at_end": b is not None}
        if b is not None:
            ww.update(peak_bytes=b["peak_bytes"],
                      compiles=b["compiles"] - a["compiles"],
                      written_bytes=b["written_bytes"])
            w.compiles += ww["compiles"]
            w.peak_bytes = max(w.peak_bytes, b["peak_bytes"])
        w.workers.append(ww)
    report(w)
    return w


def report(w: FleetWindow) -> None:
    fin = sum(1 for s in w.streams if len(s.tokens) >= s.req.max_new)
    say(f"open loop: {len(w.streams)} requests sent, {fin} finished, the "
        f"generator at most {1e3 * w.late_s:.1f} ms late; per worker "
        + "; ".join(f"{x['name']}: " + (
            f"{x['compiles']} compiles, peak {x['peak_bytes']} B, written {x['written_bytes']} B"
            if x["read_at_end"] else "not read") for x in w.workers))
    if w.kill_t is not None:
        say(f"w{w.lost} SIGKILLed at {w.kill_t - w.t0:.3f} s after "
            f"{w.kill_tokens} tokens; recover_worker "
            + (f"at +{1e3 * (w.recover[0] - w.kill_t):.1f} ms for "
               f"{1e3 * w.recover[1]:.1f} ms" if w.recover else "never ran")
            + f"; {len(w.migrate)} streams migrated")
    for key in ("frontend.workers_failed", "frontend.streams_migrated",
                "sched.prefill_tokens"):
        a, b = (merged_counter(w.stats[k], key) for k in ("start", "end"))
        say(f"{key}: {a:.0f} at the window's start, {b:.0f} at its end")
