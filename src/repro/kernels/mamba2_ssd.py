"""Pallas TPU kernel: chunked Mamba2 SSD scan (Zamba2 backbone).

Scalar per-head decay makes the chunked form three MXU matmuls per chunk:

    G = C B^T                       (C,N)x(N,C)
    y_intra = (G . e^{L_i-L_j} . mask) @ (dt*x)      (C,C)x(C,P)
    y_inter = (C . e^{L}) @ S^T                      (C,N)x(N,P)
    S'      = e^{Ltot} S + (dt*x)^T (B e^{Ltot-L})   (P,C)x(C,N)

Grid (B*H, T/C); fp32 (P, N) state in VMEM scratch across the sequential
chunk axis.  dt is folded into x and the decay exponent host-side, so the
kernel streams four aligned tensors.  VMEM per step ~ (C,P)+(C,N)x2+
(P,N)+(C,C) fp32 ~ 100 KiB at C=64, P=N=64.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 64
_EXACT = jax.lax.Precision.HIGHEST


def _ssd_kernel(dtx_ref, adt_ref, b_ref, c_ref, y_ref, s_out_ref, state_scr,
                *, chunk: int, nc: int, t: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    dtx = dtx_ref[0].astype(jnp.float32)    # (C, P)  dt_j * x_j
    adt = adt_ref[0].astype(jnp.float32)    # (C, P)  A*dt broadcast over P
    bm = b_ref[0].astype(jnp.float32)       # (C, N)
    cm = c_ref[0].astype(jnp.float32)       # (C, N)
    rows = ci * chunk + jax.lax.broadcasted_iota(jnp.int32, dtx.shape, 0)
    live = rows < t
    dtx = jnp.where(live, dtx, 0.0)
    adt = jnp.where(live, adt, 0.0)         # identity decay on padding
    brow = ci * chunk + jax.lax.broadcasted_iota(jnp.int32, bm.shape, 0)
    bm = jnp.where(brow < t, bm, 0.0)

    c = adt.shape[0]
    ii = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    # running log-decay as MXU prefix-sum matmuls (Mosaic has no
    # cumsum): L as a (C, 1) column and, contracted the other way, as a
    # (1, C) row, so e^{L_i - L_j} needs no transpose
    tril = jnp.where(jj <= ii, 1.0, 0.0)
    a1 = adt[:, :1]                          # (C, 1)
    L = jax.lax.dot_general(tril, a1, (((1,), (0,)), ((), ())),
                            precision=_EXACT,
                            preferred_element_type=jnp.float32)  # (C, 1)
    L_row = jax.lax.dot_general(a1, tril, (((0,), (1,)), ((), ())),
                                precision=_EXACT,
                                preferred_element_type=jnp.float32)  # (1, C)
    Ltot = L[-1:, :]                         # (1, 1)
    # the chunk total once more as a (P, 1) column: scaling S by a (1, 1)
    # would broadcast over sublanes and lanes at once, which Mosaic lacks
    Ltot_col = jax.lax.dot_general(adt, jnp.ones((c, 1), jnp.float32),
                                   (((0,), (0,)), ((), ())),
                                   precision=_EXACT,
                                   preferred_element_type=jnp.float32)

    S = state_scr[...]                       # (P, N)
    G = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (C, C)
    D = jnp.exp(L - L_row)                   # e^{L_i - L_j}; masked below
    A = jnp.where(jj <= ii, G * D, 0.0)
    y = jax.lax.dot_general(A, dtx, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (C, P)
    cdec = cm * jnp.exp(L)
    y = y + jax.lax.dot_general(cdec, S, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_ref[0] = y.astype(y_ref.dtype)

    bdec = bm * jnp.exp(Ltot - L)
    S = jnp.exp(Ltot_col) * S + jax.lax.dot_general(
        dtx, bdec, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    state_scr[...] = S

    @pl.when(ci == nc - 1)
    def _fin():
        s_out_ref[0] = state_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mamba2_pallas(
    x: jax.Array,    # (B, T, H, P)
    dt: jax.Array,   # (B, T, H)
    A: jax.Array,    # (H,)
    Bm: jax.Array,   # (B, T, N)
    Cm: jax.Array,   # (B, T, N)
    state: Optional[jax.Array] = None,
    chunk: int = CHUNK,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    b, t, h, p = x.shape
    n = Bm.shape[-1]
    assert state is None or not state.any(), \
        "mamba2_pallas starts from zero state; chain via the jnp path"
    nc = pl.cdiv(t, chunk)

    dtx = (x.astype(jnp.float32) * dt.astype(jnp.float32)[..., None])
    adt = (A[None, None, :] * dt.astype(jnp.float32))[..., None]
    adt = jnp.broadcast_to(adt, (b, t, h, p))
    dtx = jnp.moveaxis(dtx, 2, 1).reshape(b * h, t, p)
    adt = jnp.moveaxis(adt, 2, 1).reshape(b * h, t, p)

    kernel = functools.partial(_ssd_kernel, chunk=chunk, nc=nc, t=t)
    y, s_out = pl.pallas_call(
        kernel,
        grid=(b * h, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, p), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda bh, ci: (bh // h, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda bh, ci: (bh // h, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, p, n), lambda bh, ci: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, nc * chunk, p), x.dtype),
            jax.ShapeDtypeStruct((b * h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(dtx, adt, Bm, Cm)
    y = jnp.moveaxis(y.reshape(b, h, nc * chunk, p)[:, :, :t], 1, 2)
    return y, s_out.reshape(b, h, p, n)
