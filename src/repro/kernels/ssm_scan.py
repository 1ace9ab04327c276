"""Chunked linear-recurrence (SSD / RWKV6 WKV) as a Pallas TPU kernel.

Implements  S_t = diag(w_t)·S_{t-1} + k_tᵀ v_t,  y_t = q_t·S  in the
chunked parallel form (repro.models.ssm.chunked_linear_attn): grid =
(batch·heads, chunks) with the chunk axis sequential-minor; the running
state S [dk, dv] lives in VMEM scratch across chunk steps. Per chunk the
intra-chunk term is a decay-weighted [C, C] attention matrix — two MXU
matmuls — and the state update is one more. Decays arrive as log-space
values, clamped to ±30 like the reference.

Supports both semantics:
  * mamba  (bonus_u=None): y_t reads the post-update state (diag included),
  * rwkv6  (bonus_u [H, dk]): y_t reads S_{t-1} plus the bonus-u term.

Numerics mirror the jnp reference: the q'/k' rescaling is anchored per
16-row sub-block so every exponent is ≤ 0 (underflow-only — no overflow,
no decay clamping); diagonal sub-blocks are exact in log space.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SUB = 16


def _kernel(q_ref, k_ref, v_ref, w_ref, u_ref, y_ref, s_scr, *,
            c: int, rwkv: bool):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    q = q_ref[0].astype(jnp.float32)                # [c, dk]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)                # [c, dv]
    w = w_ref[0].astype(jnp.float32)                # [c, dk] log decay ≤ 0

    cum = jnp.cumsum(w, axis=0)
    tot = cum[-1:]                                   # [1, dk]
    qexp = (cum - w) if rwkv else cum

    uu = min(_SUB, c)
    n_sub = c // uu
    ii = jax.lax.broadcasted_iota(jnp.int32, (uu, uu), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (uu, uu), 1)
    tri = jj < ii if rwkv else jj <= ii
    y_rows = []
    for tblk in range(n_sub):
        lo = tblk * uu
        q_t = q[lo:lo + uu]
        qe_t = qexp[lo:lo + uu]
        # diagonal sub-block: exact log-space pairwise decays [uu, uu, dk]
        gap = qe_t[:, None, :] - cum[lo:lo + uu][None, :, :]
        pair = jnp.where(tri[:, :, None], jnp.exp(gap), 0.0)
        a_diag = jnp.einsum("id,ijd,jd->ij", q_t, pair, k[lo:lo + uu])
        if rwkv:
            u_vec = u_ref[0].astype(jnp.float32)    # [1, dk]
            diag = jnp.sum(q_t * u_vec * k[lo:lo + uu], axis=-1)
            a_diag = a_diag + diag[:, None] * jnp.where(ii == jj, 1.0, 0.0)
        y_t = jax.lax.dot_general(a_diag, v[lo:lo + uu],
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        if tblk > 0:
            base = cum[lo - 1][None, :]             # exclusive cum at start
            q_in = q_t * jnp.exp(qe_t - base)       # ≤ |q|
            k_in = k[:lo] * jnp.exp(base - cum[:lo])  # ≤ |k|
            a_off = jax.lax.dot_general(q_in, k_in, (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32)
            y_t = y_t + jax.lax.dot_general(a_off, v[:lo],
                                            (((1,), (0,)), ((), ())),
                                            preferred_element_type=jnp.float32)
        y_rows.append(y_t)
    y = jnp.concatenate(y_rows, axis=0)
    # carried-state read
    y = y + jax.lax.dot_general(q * jnp.exp(qexp), s_scr[...],
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_ref[0] = y.astype(y_ref.dtype)
    # state update
    k_out = k * jnp.exp(tot - cum)
    s_scr[...] = s_scr[...] * jnp.exp(tot).reshape(-1, 1) \
        + jax.lax.dot_general(k_out, v, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssm_scan(q, k, v, log_w, bonus_u=None, *, chunk: int = 128,
             interpret: Optional[bool] = None):
    """q,k [B,T,H,dk], v [B,T,H,dv], log_w [B,T,H,dk] -> y [B,T,H,dv].

    bonus_u [H, dk] selects RWKV semantics; None selects Mamba/SSD.
    (Final state stays in scratch — use the jnp reference when the carried
    state must be returned, e.g. at prefill→decode handoff.)
    ``interpret=None`` derives the default from the backend (compiled on
    TPU, interpreter elsewhere), as ``gcn_agg`` does.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    c = min(chunk, t)
    assert t % c == 0
    nc = t // c
    rwkv = bonus_u is not None

    def resh(x):
        return jnp.moveaxis(x, 2, 1).reshape(b * h, t, x.shape[-1])

    qf, kf, vf, wf = resh(q), resh(k), resh(v), resh(log_w)
    if rwkv:
        u = jnp.broadcast_to(bonus_u[None], (b, h, dk)).reshape(b * h, 1, dk)
    else:
        u = jnp.zeros((b * h, 1, dk), jnp.float32)

    out = pl.pallas_call(
        functools.partial(_kernel, c=c, rwkv=rwkv),
        grid=(b * h, nc),
        in_specs=[
            pl.BlockSpec((1, c, dk), lambda ih, ic: (ih, ic, 0)),
            pl.BlockSpec((1, c, dk), lambda ih, ic: (ih, ic, 0)),
            pl.BlockSpec((1, c, dv), lambda ih, ic: (ih, ic, 0)),
            pl.BlockSpec((1, c, dk), lambda ih, ic: (ih, ic, 0)),
            pl.BlockSpec((1, 1, dk), lambda ih, ic: (ih, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, c, dv), lambda ih, ic: (ih, ic, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, t, dv), q.dtype),
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        interpret=interpret,
    )(qf, kf, vf, wf, u)
    return jnp.moveaxis(out.reshape(b, h, t, dv), 1, 2)
