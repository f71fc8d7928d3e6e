"""One cell on one chip through ``Serve.local``: set-up, the measured
window, and what the metrics and the check need from it.

The timed entry is the program's own: ``LocalServe.step`` ->
``PagedServeScheduler.step`` -> the scheduler's jitted
``paged_decode_step`` (``_paged_fn``).  The harness calls that jit
unchanged; it only encloses each call in a host span that says which of
its two shapes it is (``bench.call_decode``, the T=1 step over every
slot, or ``bench.call_prefill``, one ``PREFILL_CHUNK``-row (128) chunk of
an admission's prompt at B=1), from which the trace reduction names the
device's runs of the program.  ``_paged_prefill`` is wrapped to record
which positions each admission prefilled.

This is the ``local`` driver: a configuration whose ``serving`` block
names no ``driver`` runs here (``bench/run.py``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import re
import sys
import tempfile
import time
from typing import Dict, List, Optional, Tuple

from harness import tracefile
from harness.device import CompileClock, stamp
from harness.traffic import Generator, Request, warmup_rounds

# the profiler trace of a ``--trace 1`` run: from this share of the window
# on, for this many seconds (longer until it holds an admission)
TRACE_AT, TRACE_SECONDS = 0.4, 4.0


def arch_of(cfg: Dict):
    """The program's ``ArchConfig`` of ``cfg``: every field of it that the
    file gives, the ``mla`` and ``moe`` blocks as their spec types."""
    from repro.configs.base import ArchConfig, MLASpec, MoESpec

    specs = {"mla": MLASpec, "moe": MoESpec}
    kw = {}
    for f in dataclasses.fields(ArchConfig):
        if f.name in cfg:
            v = cfg[f.name]
            kw[f.name] = (specs[f.name](**v) if f.name in specs
                          and v is not None else v)
    return ArchConfig(**kw)


def model_seed(seed: int) -> int:
    """The weights' seed: the run's seed as the program's ``PRNGKey``
    takes it (its low 32 bits)."""
    return int(seed) & 0xFFFFFFFF


def annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class Stream:
    req: Request
    sid: int
    due: float
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    # moved off a lost worker and resumed on another (the fleet driver)
    migrated: bool = False

    @property
    def plen(self) -> int:
        return len(self.req.prompt)


@dataclasses.dataclass
class Window:
    """What one measured window leaves behind (plain data only)."""
    t0: float
    t1: float
    streams: List[Stream]
    steps: List[List[int]]                 # decode positions, per step
    prefills: List[Tuple[int, int]]        # (t0, t1) prefilled, per call
    compiles: int
    trace: Optional[Dict] = None
    trace_steps: Tuple[int, int] = (0, 0)
    trace_prefills: Tuple[int, int] = (0, 0)
    peak_bytes: int = 0
    device: Optional[Dict] = None          # as the serving processes see it


def module_name(fn) -> str:
    """The name under which a trace shows the runs of the jitted ``fn``
    (``jit__lambda`` for a jit of a lambda): the module name JAX gives a
    function of ``fn``'s name."""
    import jax

    def probe(x):
        return x

    probe.__name__ = probe.__qualname__ = fn.__name__
    text = jax.jit(probe).lower(jax.ShapeDtypeStruct((), "float32")).as_text()
    return re.search(r"module @([\w.-]+)", text).group(1)


def annotate_calls(sched) -> str:
    """Enclose each call of the scheduler's own jitted step in a host span
    naming its shape; returns the step's module name."""
    inner, slots = sched._paged_fn, sched.slots

    def paged_fn(p, pools, tables, pos, toks):
        key = "decode" if toks.shape == (slots, 1) else "prefill"
        with annotate(tracefile.CALLS[key]):
            return inner(p, pools, tables, pos, toks)

    sched._paged_fn = paged_fn
    return module_name(inner)


def record_prefills(sched, calls: List[Tuple[int, int]]) -> None:
    """Record (t0, t1) of every admission prefill into ``calls`` (the
    list of the latest window only)."""
    if not hasattr(sched, "_bench_prefills"):
        inner = sched._paged_prefill

        def paged_prefill(table, tokens, t0, t1):
            sched._bench_prefills.append((int(t0), int(t1)))
            with annotate("bench.prefill"):
                return inner(table, tokens, t0, t1)

        sched._paged_prefill = paged_prefill
    sched._bench_prefills = calls


def build(cfg: Dict, seed: int, trace: bool):
    """The serving stack of ``cfg`` with weights from ``seed``."""
    from repro.obs.trace import Tracer, set_default_tracer
    from repro.serve import Serve, ServeConfig

    # the untraced run pays no span tracing in the program
    set_default_tracer(Tracer(enabled=trace))
    s = cfg["serving"]
    scfg = ServeConfig(arch=arch_of(cfg), full_size=True,
                       seed=model_seed(seed), slots=s["slots"],
                       max_len=s["max_len"], page_tokens=s["page_tokens"],
                       pool_pages=s["pool_pages"], quantum=s["quantum"],
                       prefix=s["prefix"])
    return Serve.local(scfg)


def warm(srv, rounds: List[List[Request]]) -> None:
    """Run set-up requests to their end, round after round: every shape
    and cache path the window uses is compiled here."""
    for reqs in rounds:
        for r in reqs:
            srv.submit(r.prompt, r.max_new)
        srv.run()


class GcClock:
    """The collections of Python's oldest generation, and the seconds all
    collections took, while it is entered."""

    def __init__(self):
        self.seconds, self.full, self._t = 0.0, 0, 0.0

    def __call__(self, phase: str, info: Dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
            return
        self.seconds += time.perf_counter() - self._t
        self.full += info["generation"] == 2

    def __enter__(self) -> "GcClock":
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self)


class Loop:
    """The measured window over one stack: submits, steps, and records
    every token's time and position.  With ``trace_dir`` a profiler trace
    is taken from ``TRACE_AT`` of the window for ``TRACE_SECONDS``,
    started and stopped between steps (when no step call is pending)."""

    def __init__(self, srv, seconds: float, trace_dir: Optional[str]):
        self.srv, self.sched = srv, srv.scheduler
        self.seconds, self.trace_dir = seconds, trace_dir
        self.prefills: List[Tuple[int, int]] = []
        record_prefills(self.sched, self.prefills)
        self.live: Dict[int, Stream] = {}
        self.streams: List[Stream] = []
        self.steps: List[List[int]] = []
        # host seconds from the end of the last step (the profiler's start
        # and stop left out) to the end of each, and the prompt tokens it
        # prefilled
        self.step_s: List[Tuple[float, int]] = []
        self._win = self._traced = None
        self.trace_steps = self.trace_prefills = (0, 0)
        self.clock = CompileClock().__enter__()
        self.gc = GcClock().__enter__()
        self.t0 = self._last = time.perf_counter()
        self.end = self.t0 + seconds

    def submit(self, req: Request, due: float) -> None:
        with annotate("bench.submit"):
            sid = self.srv.submit(req.prompt, req.max_new)
        st = Stream(req, sid, due)
        self.live[sid] = st
        self.streams.append(st)

    def step(self) -> Tuple[float, List[Stream]]:
        """One scheduler step; returns its end time and the streams it
        completed."""
        n = len(self.prefills)
        with annotate("bench.step"):
            emitted = self.srv.step()
        now = time.perf_counter()
        self.step_s.append((now - self._last, sum(
            t1 - t0 for t0, t1 in self.prefills[n:])))
        pos, done = [], []
        for sid, tok in emitted:
            st = self.live[sid]
            pos.append(st.plen - 1 + len(st.tokens))
            st.times.append(now)
            st.tokens.append(tok)
            if len(st.tokens) >= st.req.max_new:
                del self.live[sid]
                done.append(st)
        self.steps.append(pos)
        if self.trace_dir is not None:
            self._trace(now)
        self._last = time.perf_counter()
        return now, done

    def _trace(self, now: float) -> None:
        import jax

        if self._traced is None:
            if now >= self.t0 + TRACE_AT * self.seconds:
                jax.profiler.start_trace(self.trace_dir)
                self._win = annotate(tracefile.WINDOW)
                self._win.__enter__()
                self._traced = now
                self.trace_steps = (len(self.steps),) * 2
                self.trace_prefills = (len(self.prefills),) * 2
        elif self._win is not None and now >= self._traced + TRACE_SECONDS:
            # hold the trace open until it holds an admission too (every
            # per-layer metric needs something to read), up to 3x as long
            if (len(self.prefills) > self.trace_prefills[0]
                    or now >= self._traced + 3 * TRACE_SECONDS):
                self._stop_trace()

    def _stop_trace(self) -> None:
        import jax

        self._win.__exit__(None, None, None)
        self._win = None
        self.trace_steps = (self.trace_steps[0], len(self.steps))
        self.trace_prefills = (self.trace_prefills[0], len(self.prefills))
        jax.profiler.stop_trace()

    def close(self) -> Window:
        t1 = time.perf_counter()
        if self._win is not None:
            self._stop_trace()
        self.clock.__exit__(None, None, None)
        self.gc.__exit__(None, None, None)
        self._report()
        return Window(
            t0=self.t0, t1=t1, streams=self.streams, steps=self.steps,
            prefills=self.prefills, compiles=self.clock.programs,
            trace_steps=self.trace_steps, trace_prefills=self.trace_prefills)

    def _report(self) -> None:
        """Where a window lost time, on standard error: the slowest steps
        that prefilled nothing, the slowest that did, and the collector."""
        plain = sorted((s for s, n in self.step_s if not n), reverse=True)
        admit = sorted(self.step_s, key=lambda sn: -sn[0])
        print(f"steps: {len(self.step_s)}, {len(self.step_s) - len(plain)} "
              f"with an admission; slowest without: "
              f"{[round(s, 4) for s in plain[:3]]} s; slowest with: "
              f"{[(round(s, 3), n) for s, n in admit[:3]]} (s, tokens); "
              f"garbage collector: {self.gc.full} full collections, "
              f"{self.gc.seconds:.3f} s in all", file=sys.stderr, flush=True)


def closed_loop(srv, mix: Dict, seed: int, seconds: float,
                trace_dir: Optional[str]) -> Window:
    """``clients`` clients, each sending its next request as soon as its
    last completes."""
    gen = Generator(mix, seed, srv.arch.vocab_size)
    loop = Loop(srv, seconds, trace_dir)
    for _ in range(int(mix["clients"])):
        loop.submit(gen.next(), loop.t0)
    while True:
        now, done = loop.step()
        if now >= loop.end:
            return loop.close()
        for _ in done:
            loop.submit(gen.next(), now)


def run_window(cfg: Dict, mix: Dict, seed: int, seconds: float,
               trace: bool, chips: Optional[int] = None,
               on_built=None) -> Window:
    """Check the device (``stamp(chips)``), set up, measure, read the
    peak, and close the stack.  ``on_built`` (tests only) is called with
    the built stack before warm-up."""
    import jax

    from harness.device import peak_bytes

    device = stamp(chips)
    t = time.perf_counter()
    with CompileClock() as clock:
        srv = build(cfg, seed, trace)
        jax.block_until_ready(srv.params)
        built = time.perf_counter() - t
    try:
        module = annotate_calls(srv.scheduler)
        if on_built is not None:
            on_built(srv)
        with clock:
            warm(srv, warmup_rounds(mix, seed, cfg["vocab_size"]))
        print(f"set-up: stack built in {built:.1f} s, warm-up in "
              f"{time.perf_counter() - t - built:.1f} s; {clock.programs} "
              f"programs compiled or loaded from the cache in "
              f"{clock.seconds:.1f} s", file=sys.stderr, flush=True)
        with contextlib.ExitStack() as stack:
            tdir = (stack.enter_context(tempfile.TemporaryDirectory(
                prefix="bench_trace_")) if trace else None)
            w = closed_loop(srv, mix, seed, seconds, tdir)
            if tdir is not None and w.trace_steps[1] > w.trace_steps[0]:
                w.trace = tracefile.extract(tdir, module)
        w.peak_bytes, w.device = peak_bytes(), device
    finally:
        srv.close()
    return w
