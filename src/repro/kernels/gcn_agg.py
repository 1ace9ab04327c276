"""Bipartite GCN aggregation (paper Eq. 12) as a fused Pallas TPU kernel.

The paper's hot loop: degree-normalized neighbor aggregation + the
concat-linear + ReLU, batched over replay minibatches. On TPU the right
shape is a *dense masked matmul* chain feeding the MXU:

    agg = (A @ Hn) / (deg + eps);  out = relu(Hs @ Ws + agg @ Wn + b)

Fused in one kernel: the [M, O] adjacency tile, both feature tiles and
both weight tiles live in VMEM; one graph per grid step (M, O are tens —
a replay minibatch of 64 graphs is the batch axis).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(adj_ref, hs_ref, hn_ref, ws_ref, wn_ref, b_ref, o_ref):
    adj = adj_ref[0].astype(jnp.float32)            # [M, O]
    hn = hn_ref[0].astype(jnp.float32)              # [O, Fn]
    hs = hs_ref[0].astype(jnp.float32)              # [M, Fs]
    deg = jnp.sum(adj, axis=-1, keepdims=True)
    agg = jax.lax.dot_general(adj, hn, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    agg = agg / (deg + 1e-6)
    pre = jax.lax.dot_general(hs, ws_ref[...], (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    pre = pre + jax.lax.dot_general(agg, wn_ref[...],
                                    (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    o_ref[0] = jax.nn.relu(pre + b_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gcn_agg(adj, self_feat, nbr_feat, w_self, w_nbr, bias, *,
            interpret: Optional[bool] = None):
    """adj [B,M,O], self_feat [B,M,Fs], nbr_feat [B,O,Fn],
    w_self [Fs,H], w_nbr [Fn,H], bias [H] -> relu'd [B,M,H].

    ``interpret=None`` derives the default from the backend (compiled on
    TPU, interpreter elsewhere) — the same rule ``ops.py`` applies, so a
    direct caller on TPU gets the real kernel, not the interpreter.

    The bias enters as a [1, H] row: every block then spans its array's
    last two dims, which is what Mosaic requires, also after ``vmap``
    over per-cell weights adds a leading batch dim to every operand.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, m, o = adj.shape
    fs = self_feat.shape[-1]
    fn = nbr_feat.shape[-1]
    h = w_self.shape[-1]
    return pl.pallas_call(
        _kernel,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, m, o), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, m, fs), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, o, fn), lambda i: (i, 0, 0)),
            pl.BlockSpec((fs, h), lambda i: (0, 0)),
            pl.BlockSpec((fn, h), lambda i: (0, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, m, h), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, m, h), self_feat.dtype),
        interpret=interpret,
        name="gcn_agg",
    )(adj, self_feat, nbr_feat, w_self, w_nbr, bias.reshape(1, h))
