"""The unified serving construction API: one config, two entrypoints.

Serving grew three hand-wired construction paths — ``ServeEngine``
(lockstep batch surface), ``PagedServeScheduler`` + ``KVPager`` +
``PrefixCache`` (continuous batching), and ``FleetFrontend.launch`` over
``WorkerSpec`` lists (multi-process) — each with overlapping but
divergent kwargs.  This module folds them behind one declarative
:class:`ServeConfig` and two entrypoints:

* :func:`Serve.local` — one in-process scheduler (paged or contiguous),
  with the pager/prefix/session plumbing built from the config.
* :func:`Serve.fleet` — N spawned workers behind a
  :class:`~repro.serve.fleet.frontend.FleetFrontend`, each worker built
  from the *same* config (so the fleet serves one model), with the
  elastic-resilience knobs (epoch checkpoint cadence, heartbeat pacing,
  adoption throttle) carried through.

The old constructors keep working — ``ServeEngine`` warns once per
process and forwards unchanged — so existing callers migrate at their
own pace while new code states *what* to serve, not how to wire it.
"""

from __future__ import annotations

import dataclasses
import tempfile
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

from repro.configs.base import ArchConfig


@dataclass(frozen=True)
class ServeConfig:
    """Everything needed to build a serving stack, local or fleet.

    Model side: ``arch`` is a registry name or an
    :class:`~repro.configs.base.ArchConfig` (built ``reduced()`` unless
    ``full_size``; its ``param_dtype`` is the dtype the weights are held
    in); ``seed`` is the params seed (fleet workers must share it —
    migration correctness rests on identical params).

    Scheduler side: ``paged`` picks the in-jit page-pool decode loop
    (``PagedServeScheduler``) over the contiguous lane path; ``spec_k``
    > 0 adds speculative multi-token verification (implies paged);
    ``kv_codec`` is the KV representation policy (``"zlib"`` lossless,
    ``"int8"`` quantized residency).

    Memory side: ``fast_bytes`` sizes the pager's fast tier (``None``
    auto-sizes to ``slots + 1`` serialized lanes — enough to decode,
    tight enough that oversubscription spills); ``prefix`` enables the
    shared-prefix radix cache.

    Fleet side (ignored by :func:`Serve.local`): ``shared_capacity``
    bounds the cross-process domain, ``ckpt_every`` > 0 enables each
    worker's periodic epoch checkpoint (the recovery-stall bound),
    ``hb_interval_s`` / ``hb_timeout_s`` pace the failure detector, and
    ``adopt_batch`` > 0 throttles per-admission board adoption."""

    arch: Union[str, ArchConfig] = "phi3-mini-3.8b"
    seed: int = 0
    full_size: bool = False
    # scheduler
    paged: bool = True
    slots: int = 2
    max_len: int = 32
    quantum: int = 3
    page_tokens: int = 4
    pool_pages: Optional[int] = None
    spec_k: int = 0
    kv_codec: Optional[str] = None
    # memory
    fast_bytes: Optional[int] = None
    page_bytes: int = 8 * 1024
    prefix: bool = True
    # fleet / resilience
    shared_capacity: int = 1 << 30
    ckpt_every: int = 0
    hb_interval_s: float = 0.25
    hb_timeout_s: float = 2.0
    adopt_batch: int = 0

    def resolved_arch(self) -> ArchConfig:
        """The architecture this config serves, cut to ``reduced()``
        unless ``full_size``."""
        from repro.configs import get_config

        arch = get_config(self.arch) if isinstance(self.arch, str) \
            else self.arch
        return arch if self.full_size else arch.reduced()

    def worker_spec(self, shared_root: str, name: str = "",
                    chip: Optional[int] = None) -> Any:
        """The per-worker spawn spec this config denotes; ``chip`` is the
        host chip the worker is confined to (``None``: no confinement)."""
        from repro.serve.fleet.worker import WorkerSpec

        return WorkerSpec(
            shared_root=str(shared_root), arch=self.resolved_arch(),
            chip=chip, slots=self.slots,
            max_len=self.max_len, page_tokens=self.page_tokens,
            quantum=self.quantum, pool_pages=self.pool_pages,
            spec_k=self.spec_k,
            fast_bytes=self.fast_bytes or 8 << 20,
            page_bytes=self.page_bytes, kv_codec=self.kv_codec,
            shared_capacity=self.shared_capacity, seed=self.seed,
            name=name, ckpt_every=self.ckpt_every,
            hb_interval_s=self.hb_interval_s, adopt_batch=self.adopt_batch)


def _build_model(cfg: ServeConfig) -> Tuple[Any, Any, Any]:
    import jax

    from repro.models.registry import get_model

    arch = cfg.resolved_arch()
    model = get_model(arch)
    params = model.init(jax.random.PRNGKey(cfg.seed), arch)
    return arch, model, params


class LocalServe:
    """One in-process serving stack built from a :class:`ServeConfig`.

    Exposes the scheduler's continuous-batching surface (submit / step /
    run / output) plus the wiring (:attr:`scheduler`, :attr:`pager`,
    :attr:`prefix_cache`) for callers that need the internals.  Context
    manager: closing tears down the scheduler and its stack."""

    def __init__(self, cfg: ServeConfig, session: Any = None):
        from repro.io.serialization import serialize_state
        from repro.serve.kvpage import KVPager
        from repro.serve.prefix import PrefixCache
        from repro.serve.scheduler import PagedServeScheduler, ServeScheduler

        import jax

        self.cfg = cfg
        self.arch, self.model, self.params = _build_model(cfg)
        fast = cfg.fast_bytes
        if fast is None:
            lane_bytes = serialize_state(jax.device_get(
                self.model.init_cache(self.arch, 1, cfg.max_len))).nbytes
            fast = (cfg.slots + 1) * lane_bytes
        self.pager = KVPager.for_capacity(fast_bytes=fast,
                                          page_bytes=cfg.page_bytes)
        self.prefix_cache = None
        if cfg.prefix:
            self.prefix_cache = PrefixCache.for_model(
                self.pager.stack, self.arch, self.model, cfg.max_len,
                page_tokens=cfg.page_tokens)
        if cfg.paged or cfg.spec_k > 0:
            self.scheduler = PagedServeScheduler(
                self.arch, self.model, self.params, slots=cfg.slots,
                max_len=cfg.max_len, pager=self.pager, session=session,
                quantum=cfg.quantum, prefix=self.prefix_cache,
                page_tokens=cfg.page_tokens, pool_pages=cfg.pool_pages,
                spec_k=cfg.spec_k, kv_codec=cfg.kv_codec)
        else:
            self.scheduler = ServeScheduler(
                self.arch, self.model, self.params, slots=cfg.slots,
                max_len=cfg.max_len, pager=self.pager, session=session,
                quantum=cfg.quantum, prefix=self.prefix_cache)

    # -- the scheduler surface, re-exported -------------------------------- #

    def submit(self, prompt: Sequence[int], max_new: int,
               weight: int = 1) -> int:
        return self.scheduler.submit(prompt, max_new, quantum_weight=weight)

    def step(self) -> List[Tuple[int, int]]:
        return self.scheduler.step()

    def run(self, max_steps: Optional[int] = None) -> int:
        return self.scheduler.run(max_steps=max_steps)

    def output(self, sid: int) -> List[int]:
        return self.scheduler.output(sid)

    def save(self, session: Any = None):
        return self.scheduler.save(session)

    def restore(self, session: Any = None, step: Optional[int] = None):
        return self.scheduler.restore(session, step=step)

    @property
    def stats(self) -> Dict[str, Any]:
        return self.scheduler.stats

    def close(self) -> None:
        self.scheduler.close()

    def __enter__(self) -> "LocalServe":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class Serve:
    """The two serving entrypoints (namespace class — no instances)."""

    @staticmethod
    def local(cfg: ServeConfig, session: Any = None) -> LocalServe:
        """One in-process scheduler wired from ``cfg``.  ``session`` is
        an optional :class:`~repro.api.session.ResilienceSession` for
        checkpoint/restore through the scheduler's save/restore."""
        return LocalServe(cfg, session=session)

    @staticmethod
    def fleet(cfg: ServeConfig, workers: int = 2,
              shared_root: Optional[str] = None,
              quotas: Optional[Dict[str, Any]] = None,
              classes: Optional[Dict[str, Any]] = None,
              ready_timeout: float = 600.0, **frontend_kw) -> Any:
        """N spawned workers over one shared cache domain behind a
        :class:`~repro.serve.fleet.frontend.FleetFrontend`.  The
        frontend's failure detector inherits ``cfg.hb_timeout_s``;
        workers inherit the epoch-checkpoint cadence, so a fleet built
        here is elastic out of the box when ``cfg.ckpt_every`` > 0.
        ``shared_root`` defaults to a fresh temp dir (use an explicit
        path to join an existing domain).  Worker ``i`` is confined to
        chip ``i`` of the host, so a fleet on TPU has at most one worker
        per chip; the frontend itself never touches JAX."""
        if workers < 1:
            raise ValueError("need at least one worker")
        if shared_root is None:
            shared_root = tempfile.mkdtemp(prefix="deeper_fleet_")
        from repro.serve.fleet.frontend import FleetFrontend

        # worker i holds chip i of the host: one process per chip
        specs = [cfg.worker_spec(shared_root, name=f"w{i}", chip=i)
                 for i in range(workers)]
        kw = dict(frontend_kw)
        kw.setdefault("hb_timeout_s", cfg.hb_timeout_s)
        if quotas is not None:
            kw["quotas"] = quotas
        if classes is not None:
            kw["classes"] = classes
        return FleetFrontend.launch(specs, ready_timeout=ready_timeout, **kw)

    @staticmethod
    def stats(handle: Any) -> Dict[str, Any]:
        """The unified observability view over either entrypoint's
        handle: ``{"merged": <registry snapshot>, "frontend"/"local":
        <snapshot>, "workers": {name: <snapshot>}}``.  For a fleet this
        is :meth:`FleetFrontend.fleet_stats` (worker snapshots merged
        sketch-wise); for a local stack the single registry is its own
        merge."""
        fleet_stats = getattr(handle, "fleet_stats", None)
        if callable(fleet_stats):
            return fleet_stats()
        snap = handle.scheduler.registry.snapshot()
        return {"merged": snap, "local": snap, "workers": {}}


__all__ = ["LocalServe", "Serve", "ServeConfig"]
