"""Flash attention (GQA, causal, optional sliding window) as a Pallas TPU
kernel.

Tiling: grid = (batch·q_heads, q_blocks, kv_blocks); the kv axis is the
minor (sequential) grid dimension, so the online-softmax running state
(m, l, acc) lives in VMEM scratch and is carried across kv steps — the
standard TPU flash scheme. GQA is handled in the BlockSpec index maps:
the kv block for q-head ``h`` loads kv-head ``h // group``, so shared K/V
tiles are streamed once per group without materializing an expanded K/V.

Block shapes default to (128, head_dim) — MXU-aligned (multiples of 8×128
for f32/bf16 tiles).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window, bq: int, bk: int,
            nk: int, seq_len: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                 # [bq, d]
    k = k_ref[0].astype(jnp.float32)                 # [bk, d]
    v = v_ref[0].astype(jnp.float32)                 # [bk, d]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    ok = k_pos < seq_len
    if causal:
        ok &= k_pos <= q_pos
    if window is not None:
        ok &= (q_pos - k_pos) < window
    s = jnp.where(ok, s, _NEG)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / (l_scr[...][:, None] + 1e-30)).astype(
            o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None):
    """q [B,S,H,d], k/v [B,S,KVH,d] -> [B,S,H,d].

    ``interpret=None`` derives the default from the backend (compiled on
    TPU, interpreter elsewhere), as ``gcn_agg`` does.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, s, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    scale = 1.0 / math.sqrt(d)
    bq = min(block_q, s)
    bk = min(block_k, s)
    assert s % bq == 0 and s % bk == 0
    nq, nk = s // bq, s // bk

    # [B,S,H,d] -> [B*H, S, d] with h-major layout for clean index maps
    qf = jnp.moveaxis(q, 2, 1).reshape(b * h, s, d)
    kf = jnp.moveaxis(k, 2, 1).reshape(b * kvh, s, d)
    vf = jnp.moveaxis(v, 2, 1).reshape(b * kvh, s, d)

    def q_map(ih, iq, ik):
        return (ih, iq, 0)

    def kv_map(ih, iq, ik):
        return (ih // g, ik, 0)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, window=window,
                          bq=bq, bk=bk, nk=nk, seq_len=s),
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), q_map),
            pl.BlockSpec((1, bk, d), kv_map),
            pl.BlockSpec((1, bk, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, d), q_map),
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return jnp.moveaxis(out.reshape(b, h, s, d), 1, 2)
