"""Single-token decode attention over a long KV cache (Pallas TPU).

The memory-bound hot loop of serving: one query token per sequence
streaming the KV cache from HBM through VMEM in (block_k × head_dim)
tiles, online-softmax accumulated in VMEM scratch. Grid =
(batch·q_heads, kv_blocks) with the kv axis sequential-minor. GQA via
index maps (kv head = q head // group), as in flash_attention.
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


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, bk: int, nk: int):
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                # [1, d]
    k = k_ref[0].astype(jnp.float32)                # [bk, d]
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    s = jnp.where(k_pos < len_ref[0], s, _NEG)

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


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(q, k, v, lengths, *, block_k: int = 256,
                     interpret: Optional[bool] = None):
    """q [B,H,d] (one token), k/v [B,S,KVH,d], lengths [B] -> [B,H,d].

    ``interpret=None`` derives the default from the backend (compiled on
    TPU, interpreter elsewhere), as ``gcn_agg`` does.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, h, d = q.shape
    s, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    scale = 1.0 / math.sqrt(d)
    bk = min(block_k, s)
    assert s % bk == 0
    nk = s // bk

    qf = q.reshape(b * h, 1, d)
    kf = jnp.moveaxis(k, 2, 1).reshape(b * kvh, s, d)
    vf = jnp.moveaxis(v, 2, 1).reshape(b * kvh, s, d)
    lens = jnp.repeat(lengths, h).astype(jnp.int32)   # [B*H]

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, bk=bk, nk=nk),
        grid=(b * h, nk),
        in_specs=[
            pl.BlockSpec((1,), lambda ih, ik: (ih,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, d), lambda ih, ik: (ih, 0, 0)),
            pl.BlockSpec((1, bk, d), lambda ih, ik: (ih // g, ik, 0)),
            pl.BlockSpec((1, bk, d), lambda ih, ik: (ih // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, d), lambda ih, ik: (ih, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, 1, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
        ],
        interpret=interpret,
    )(lens, qf, kf, vf)
    return out.reshape(b, h, d)
