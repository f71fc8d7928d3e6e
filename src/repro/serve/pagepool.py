"""DevicePagePool: one pooled device KV buffer + a host-side page allocator.

The contiguous serving path keeps one lane cache per slot and gathers a
parked stream's KV back into its lane on resume — bytes move on every
park/resume cycle even when nothing changed.  This module is the other
half of the DEEP-ER argument: keep the data where it lives and move only
*references*.  Every stream's KV lives in one shared device buffer per
cache leaf, laid out as physical pages of ``page_tokens`` tokens:

    leaf (L, B=1, S, *rest)  ->  pool (L, P, page_tokens, *rest)

A stream is a row of a page *table* (logical page j -> physical slot);
the jitted decode step (``models.transformer.paged_decode_step``) reads
and writes straight through the tables, so admit / park / resume are
pure host-side bookkeeping on this allocator — zero device traffic.

Sharing: a pool-resident prefix page (serve/prefix.py) is bound to its
chain digest here; every stream admitted with that prefix points its
table at the *same* physical slot and bumps its refcount.  A page is
freed when no table row and no digest binding references it.

Physical slot :data:`TRASH_PAGE` (0) is reserved as the *trash page*,
which the paged step (``models/transformer.py``) defines: inactive
scheduler lanes point their whole table at it, and the step sends the
rows it must discard there (an admission-prefill chunk's pad rows, fed
``PAD_TOKEN``, and any row past a lane's last slot), so discarded writes
never land in a live stream's pages.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.memory.codecs import SCALE_SUFFIX, int8_dequantize, int8_quantize
from repro.memory.tiers import CapacityError
from repro.models.transformer import TRASH_PAGE


class DevicePagePool:
    """Fixed-capacity pool of KV pages on device + host allocator.

    ``lane_template`` is one lane's cache pytree (``model.init_cache(cfg,
    1, max_len)``); every leaf must be laid out ``(layers, batch=1,
    kv_seq, *rest)`` (``model.cache_axes``) — the transformer-family
    layout.  ``n_pages`` is the physical capacity *excluding* the trash
    page.

    ``quantized=True`` is the int8 residency mode: each K/V leaf is held
    on device as int8 with one float32 scale per last-axis channel in a
    parallel ``<name>__scale`` buffer (both live in :attr:`leaves`, so
    the jitted decode step, checkpoint snapshot/load, and shape
    templates see them like any other leaf).  The byte interchange with
    the KVPager (:meth:`page_blob` / :meth:`write_blob`) stays in
    *decoded* template-dtype bytes — content addressing and spill
    plumbing never see the quantized representation — while the device
    cost per page (:attr:`page_device_nbytes`) drops to roughly a
    quarter (float32) / half (bf16) of :attr:`page_nbytes`, which is the
    capacity win fig10's equal-HBM section measures.
    """

    def __init__(self, lane_template: Any, axes: Any, page_tokens: int,
                 n_pages: int, quantized: bool = False):
        if page_tokens < 1:
            raise ValueError("page_tokens must be >= 1")
        if n_pages < 1:
            raise ValueError("n_pages must be >= 1")
        leaves = {}
        flat_t = jax.tree_util.tree_flatten(lane_template)[0]
        flat_a = jax.tree_util.tree_flatten(
            axes, is_leaf=lambda x: isinstance(x, tuple))[0]
        names = sorted(lane_template)   # transformer caches are flat dicts
        if len(names) != len(flat_t):
            raise ValueError("pool requires a flat dict cache layout")
        max_len = None
        dtypes: Dict[str, np.dtype] = {}
        for name, leaf, ax in zip(names, flat_t, flat_a):
            if name.endswith(SCALE_SUFFIX):
                raise ValueError(
                    f"leaf name {name} collides with the scale-buffer suffix")
            if len(ax) < 3 or ax[0] != "layers" or ax[2] != "kv_seq":
                raise ValueError(
                    f"leaf {name}: pool needs (layers, batch, kv_seq, ...) "
                    f"layout, got axes {ax}")
            arr = np.asarray(leaf)
            n_layers, b, s = arr.shape[:3]
            if b != 1:
                raise ValueError("lane_template must be batch-1")
            if s % page_tokens:
                raise ValueError(
                    f"max_len {s} not a multiple of page_tokens {page_tokens}")
            if max_len is not None and s != max_len:
                raise ValueError("cache leaves disagree on kv_seq length")
            if quantized and len(arr.shape) < 4:
                raise ValueError(
                    f"leaf {name}: quantized mode needs a channel axis "
                    f"after kv_seq, got shape {arr.shape}")
            max_len = s
            dtypes[name] = arr.dtype
            shape = (n_layers, 1 + n_pages, page_tokens) + arr.shape[3:]
            if quantized:
                leaves[name] = jnp.zeros(shape, jnp.int8)
                leaves[name + SCALE_SUFFIX] = jnp.zeros(
                    shape[:-1], jnp.float32)
            else:
                leaves[name] = jnp.zeros(shape, arr.dtype)
        self.leaves: Dict[str, jax.Array] = leaves
        self.quantized = bool(quantized)
        # the decoded (template) dtypes and leaf names, scale buffers
        # excluded — the byte-interchange layout
        self.dtypes = dtypes
        self.data_names = sorted(dtypes)
        self.page_tokens = int(page_tokens)
        self.n_pages = int(n_pages)
        self.max_len = int(max_len)
        self.pages_per_lane = self.max_len // self.page_tokens
        # logical page size: decoded bytes, the KVPager interchange unit
        self.page_nbytes = sum(
            int(np.prod(leaves[n].shape[2:], dtype=np.int64))
            * dtypes[n].itemsize * leaves[n].shape[0]
            for n in self.data_names)
        # physical page size: what one page actually costs on device
        # (int8 payload + float32 scales in quantized mode)
        self.page_device_nbytes = sum(
            int(np.prod(l.shape[2:], dtype=np.int64)) * l.dtype.itemsize
            * l.shape[0] for l in leaves.values())
        self._refs: Dict[int, int] = {}            # phys -> refcount
        self._free: List[int] = list(range(1, 1 + n_pages))
        self._digest_phys: Dict[str, int] = {}     # prefix digest -> phys

    # -- allocator --------------------------------------------------------- #

    def alloc(self, n: int) -> List[int]:
        """Allocate ``n`` physical pages (refcount 1 each); all-or-nothing."""
        if n > len(self._free):
            raise CapacityError(
                f"pool exhausted: want {n} pages, {len(self._free)} free")
        out = [self._free.pop() for _ in range(n)]
        for phys in out:
            self._refs[phys] = 1
        return out

    def ref(self, phys: int) -> None:
        assert phys != TRASH_PAGE and phys in self._refs, phys
        self._refs[phys] += 1

    def deref(self, phys: int) -> None:
        if phys == TRASH_PAGE:
            return
        self._refs[phys] -= 1
        if self._refs[phys] <= 0:
            del self._refs[phys]
            self._free.append(phys)

    def free_pages(self) -> int:
        return len(self._free)

    def used_pages(self) -> int:
        return self.n_pages - len(self._free)

    def refcount(self, phys: int) -> int:
        return self._refs.get(phys, 0)

    def refcounts(self) -> Dict[int, int]:
        """Every allocated page's refcount (checkpoint meta)."""
        return dict(sorted(self._refs.items()))

    # -- prefix-page residency --------------------------------------------- #

    def bind_digest(self, digest: str, phys: int) -> None:
        """Pin a physical page as the pool-resident copy of a prefix
        digest (holds one reference until :meth:`drop_digest`)."""
        assert digest not in self._digest_phys
        self.ref(phys)
        self._digest_phys[digest] = phys

    def lookup_digest(self, digest: str) -> Optional[int]:
        return self._digest_phys.get(digest)

    def drop_digest(self, digest: str) -> None:
        phys = self._digest_phys.pop(digest, None)
        if phys is not None:
            self.deref(phys)

    def resident_digests(self) -> Dict[str, int]:
        return dict(self._digest_phys)

    # -- page I/O (park/spill paths only — never the decode hot loop) ------ #

    def _store_decoded(self, phys: int, name: str, arr: np.ndarray) -> None:
        """Write one leaf's decoded page slice (L, pt, *rest) into slot
        ``phys`` — quantizing per channel (last axis) in quantized mode."""
        leaf = self.leaves[name]
        if self.quantized:
            q, scale = int8_quantize(np.asarray(arr), axis=-1)
            self.leaves[name] = leaf.at[:, phys].set(q)
            sleaf = self.leaves[name + SCALE_SUFFIX]
            self.leaves[name + SCALE_SUFFIX] = sleaf.at[:, phys].set(
                scale[..., 0])
        else:
            self.leaves[name] = leaf.at[:, phys].set(
                jnp.asarray(arr, leaf.dtype))

    def read_page(self, phys: int) -> Dict[str, np.ndarray]:
        """One physical page's per-leaf host arrays, each (L, pt, *rest),
        always in the *decoded* template dtype."""
        out = {}
        for name in self.data_names:
            arr = np.asarray(jax.device_get(self.leaves[name][:, phys]))
            if self.quantized:
                scale = np.asarray(jax.device_get(
                    self.leaves[name + SCALE_SUFFIX][:, phys]))
                arr = np.asarray(int8_dequantize(
                    arr, scale[..., None])).astype(self.dtypes[name])
            out[name] = arr
        return out

    def page_blob(self, phys: int) -> bytes:
        """One physical page as bytes (leaves concatenated in sorted
        name order) — the interchange unit with the KVPager.  Decoded
        bytes even in quantized mode: the pager's content addressing and
        the tier codecs operate above the pool's device representation."""
        page = self.read_page(phys)
        return b"".join(page[n].tobytes() for n in self.data_names)

    def write_page(self, phys: int, page: Dict[str, np.ndarray]) -> None:
        for name, arr in page.items():
            self._store_decoded(phys, name, np.asarray(arr))

    def write_blob(self, phys: int, blob: bytes) -> None:
        if len(blob) != self.page_nbytes:
            raise ValueError(
                f"page blob of {len(blob)} bytes != page size "
                f"{self.page_nbytes}")
        off = 0
        page = {}
        for name in self.data_names:
            leaf = self.leaves[name]
            dtype = self.dtypes[name]
            shape = (leaf.shape[0], self.page_tokens) + leaf.shape[3:]
            n = int(np.prod(shape, dtype=np.int64)) * dtype.itemsize
            page[name] = np.frombuffer(
                blob[off:off + n], dtype).reshape(shape)
            off += n
        self.write_page(phys, page)

    def write_token_slice(self, phys: int, part: Any) -> None:
        """Scatter a prefix-cache payload slice — leaves (L, 1,
        page_tokens, *rest) — into one physical page."""
        for name in self.data_names:
            self._store_decoded(phys, name, np.asarray(part[name])[:, 0])

    def read_token_slice(self, phys: int) -> Any:
        """The inverse of :meth:`write_token_slice`: a prefix-cache
        payload pytree (leaves (L, 1, page_tokens, *rest)) cut from one
        physical page."""
        return {name: arr[:, None]
                for name, arr in self.read_page(phys).items()}

    def blob_to_token_slice(self, blob: bytes) -> Any:
        """Reinterpret one page *blob* (:meth:`page_blob` layout —
        decoded bytes, leaves concatenated in sorted name order) as a
        prefix-cache payload pytree, without touching the device.  The
        epoch-checkpoint exporter uses this to register a *spilled*
        stream's parked pages straight from the pager's blobs, so
        streams off-pool at checkpoint time are recoverable on a peer
        at the same fidelity as pool-resident ones."""
        if len(blob) != self.page_nbytes:
            raise ValueError(
                f"page blob of {len(blob)} bytes != page size "
                f"{self.page_nbytes}")
        off = 0
        part = {}
        for name in self.data_names:
            leaf = self.leaves[name]
            dtype = self.dtypes[name]
            shape = (leaf.shape[0], self.page_tokens) + leaf.shape[3:]
            n = int(np.prod(shape, dtype=np.int64)) * dtype.itemsize
            part[name] = np.frombuffer(
                blob[off:off + n], dtype).reshape(shape)[:, None]
            off += n
        return part

    def write_token_range(self, phys: int, part: Any, n: int) -> None:
        """Scatter the first ``n`` tokens of a page — a partial-page
        tail payload, leaves (L, 1, n, *rest) — into slot ``phys``.
        Positions past ``n`` are untouched: they are stream-private and
        get written by the owner's suffix prefill, which is why tails
        share *compute* but never physical pages."""
        if not 0 < n <= self.page_tokens:
            raise ValueError(f"token range {n} outside (0, {self.page_tokens}]")
        for name in self.data_names:
            arr = np.asarray(part[name])[:, 0]      # (L, n, *rest)
            leaf = self.leaves[name]
            if self.quantized:
                q, scale = int8_quantize(arr, axis=-1)
                self.leaves[name] = leaf.at[:, phys, :n].set(q)
                sleaf = self.leaves[name + SCALE_SUFFIX]
                self.leaves[name + SCALE_SUFFIX] = sleaf.at[:, phys, :n].set(
                    scale[..., 0])
            else:
                self.leaves[name] = leaf.at[:, phys, :n].set(
                    jnp.asarray(arr, leaf.dtype))

    def read_token_range(self, phys: int, n: int) -> Any:
        """The first ``n`` tokens of a page as a payload pytree (leaves
        (L, 1, n, *rest)) — the tail-registration read."""
        if not 0 < n <= self.page_tokens:
            raise ValueError(f"token range {n} outside (0, {self.page_tokens}]")
        return {name: arr[:, None, :n]
                for name, arr in self.read_page(phys).items()}

    # -- checkpoint -------------------------------------------------------- #

    def snapshot(self) -> Dict[str, np.ndarray]:
        """The full pooled device buffer, byte-identical (trash page and
        unallocated slots included — restore reproduces the exact device
        state, not just the live subset)."""
        return {name: np.asarray(jax.device_get(l))
                for name, l in self.leaves.items()}

    def load(self, arrays: Dict[str, np.ndarray], refs: Dict[int, int],
             digest_phys: Dict[str, int]) -> None:
        for name, arr in arrays.items():
            leaf = self.leaves[name]
            if tuple(arr.shape) != tuple(leaf.shape):
                raise ValueError(
                    f"pool leaf {name}: snapshot shape {arr.shape} != "
                    f"pool shape {leaf.shape}")
            self.leaves[name] = jnp.asarray(arr, leaf.dtype)
        self._refs = {int(k): int(v) for k, v in refs.items()}
        self._free = [p for p in range(1, 1 + self.n_pages)
                      if p not in self._refs]
        self._digest_phys = {str(d): int(p) for d, p in digest_phys.items()}
