"""Pallas TPU kernel: chunked WKV6 recurrence (RWKV6 "Finch").

SSD-style decomposition: the per-channel data-dependent decay recurrence

    S_t = diag(w_t) S_{t-1} + k_t v_t^T ;  o_t = r_t (S_{t-1} + diag(u) k_t v_t^T)

is processed in chunks of C tokens.  Within a chunk the pairwise decay
factorizes:  A_ij = sum_d  [r e^{Lp_i - Ltot}]_id [k e^{Ltot - L_j}]_jd
(L = running log-decay, Ltot = chunk total), so intra-chunk work is three
MXU matmuls ((C,D)x(D,C), (C,C)x(C,D), (C,D)x(D,D)) — both re-centered
exponents are <= 0, so no overflow; the kernel uses chunk=16 so the
re-centering underflow floor (e^-43 at the clip w>=e^-e) stays inside
fp32 normal range.

Grid (B*H, T/C): the chunk axis is sequential on TPU; the fp32 state
matrix (D, D) lives in VMEM scratch across chunk steps.  VMEM working
set: 4 x (C, D) inputs + (D, D) state + (C, C) scores ~ 40 KiB at
C=16, D=64.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 16
_EXACT = jax.lax.Precision.HIGHEST


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, s_out_ref, state_scr,
                 *, chunk: int, nc: int, t: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    r = r_ref[0].astype(jnp.float32)      # (C, D)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)      # (1, D) -> broadcast row
    # identity decay on padded tail rows so the state stays exact
    rows = ci * chunk + jax.lax.broadcasted_iota(jnp.int32, w.shape, 0)
    w = jnp.where(rows < t, w, jnp.ones_like(w))
    k = jnp.where(rows < t, k, jnp.zeros_like(k))
    v = jnp.where(rows < t, v, jnp.zeros_like(v))

    logw = jnp.log(jnp.maximum(w, 1e-38))
    c = logw.shape[0]
    ii = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    # chunk-local prefix sums as MXU matmuls (Mosaic has no cumsum):
    # L_i = sum_{j<=i} logw_j, and the chunk total as a (D, 1) column
    L = jax.lax.dot_general(jnp.where(jj <= ii, 1.0, 0.0), logw,
                            (((1,), (0,)), ((), ())), precision=_EXACT,
                            preferred_element_type=jnp.float32)  # (C, D)
    Lp = L - logw                          # L_{i-1}
    Ltot = L[-1:, :]                       # (1, D)
    Ltot_col = jax.lax.dot_general(logw, jnp.ones((c, 1), jnp.float32),
                                   (((0,), (0,)), ((), ())), precision=_EXACT,
                                   preferred_element_type=jnp.float32)

    r_dec = r * jnp.exp(Lp)                          # for inter-chunk term
    r_ctr = r * jnp.exp(Lp - Ltot)                   # re-centered (<= 0 exp)
    k_ctr = k * jnp.exp(Ltot - L)                    # re-centered (<= 0 exp)

    S = state_scr[...]                               # (D, D)
    A = jax.lax.dot_general(r_ctr, k_ctr, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (C, C)
    A = jnp.where(jj < ii, A, 0.0)                   # strict lower triangle
    y = jax.lax.dot_general(A, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    du = jnp.sum(r * u * k, axis=-1, keepdims=True)  # diagonal bonus
    y = y + du * v
    y = y + jax.lax.dot_general(r_dec, S, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_ref[0] = y.astype(y_ref.dtype)

    S = jnp.exp(Ltot_col) * S + jax.lax.dot_general(
        k_ctr, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    state_scr[...] = S

    @pl.when(ci == nc - 1)
    def _fin():
        s_out_ref[0] = state_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6_pallas(
    r: jax.Array,   # (B, T, H, D)
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    u: jax.Array,   # (H, D)
    state: Optional[jax.Array] = None,   # only zero-init supported in-kernel
    chunk: int = CHUNK,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    b, t, h, d = r.shape
    assert state is None or not state.any(), \
        "wkv6_pallas starts from zero state; chain chunks via the jnp path"
    nc = pl.cdiv(t, chunk)

    def to_bh(x):
        return jnp.moveaxis(x, 2, 1).reshape(b * h, t, d)

    rt, kt, vt, wt = (to_bh(x) for x in (r, k, v, w))
    u2 = u.reshape(h, 1, d)

    kernel = functools.partial(_wkv6_kernel, chunk=chunk, nc=nc, t=t)
    y, s_out = pl.pallas_call(
        kernel,
        grid=(b * h, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, d), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, d), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, d), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, d), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, 1, d), lambda bh, ci: (jax.lax.rem(bh, h), 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, d), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, d, d), lambda bh, ci: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, nc * chunk, d), r.dtype),
            jax.ShapeDtypeStruct((b * h, d, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        interpret=interpret,
    )(rt, kt, vt, wt, u2)
    y = jnp.moveaxis(y.reshape(b, h, nc * chunk, d)[:, :, :t], 1, 2)
    return y, s_out.reshape(b, h, d, d)
