"""Fused bipartite edge scorer (paper Eq. 13–14) as a Pallas TPU kernel.

The actor's second hot stage: every (device, option) edge gets a score

    logits[m, o] = w_out · relu(src[m] + dst[o] + ef[m, o] * w_feat) + b_out
    src = h_dev @ W_src + b_src,   dst = h_opt @ W_dst

i.e. the concat-linear of Eq. 14 decomposed into src/dst/edge-feature
projections (mathematically identical, avoids the [M, O, 2H] concat),
followed by ReLU and the scalar output head, all in one kernel. The
[M, O, E] hidden lives only in VMEM registers per grid step — it is
never materialized in HBM, which is the entire point: the unbatched jnp
path writes it out three times per forward.

One graph per grid step (M, O are tens); a replay minibatch of 64
graphs, a candidate set, a fleet, or a packed sweep's cell axis is the
batch dimension.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(hs_ref, hd_ref, ef_ref, ws_ref, bs_ref, wd_ref, wf_ref,
            wo_ref, bo_ref, o_ref):
    hs = hs_ref[0].astype(jnp.float32)               # [M, H]
    hd = hd_ref[0].astype(jnp.float32)               # [O, H]
    ef = ef_ref[0].astype(jnp.float32)               # [M, O]
    src = jax.lax.dot_general(hs, ws_ref[...], (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    src = src + bs_ref[...]                          # [M, E]
    dst = jax.lax.dot_general(hd, wd_ref[...], (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # [O, E]
    x = src[:, None, :] + dst[None, :, :] + ef[..., None] * wf_ref[...]
    out = jnp.sum(jnp.maximum(x, 0.0) * wo_ref[...], axis=-1)
    o_ref[0] = (out + bo_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def edge_score(h_src, h_dst, edge_feat, w_src, b_src, w_dst, w_feat,
               w_out, b_out, *, interpret: Optional[bool] = None):
    """h_src [B,M,H], h_dst [B,O,H], edge_feat [B,M,O]; w_src/w_dst
    [H,E], b_src/w_feat/w_out [E], b_out [1] -> logits [B,M,O].

    ``interpret=None`` derives the default from the backend (compiled on
    TPU, interpreter elsewhere), mirroring ``gcn_agg``. The vectors enter
    as [1, E] rows (and ``b_out`` as [1, 1]) for the same Mosaic block
    rule ``gcn_agg`` documents.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, m, o = edge_feat.shape
    h = h_src.shape[-1]
    e = w_src.shape[-1]
    return pl.pallas_call(
        _kernel,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, m, h), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, o, h), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, m, o), lambda i: (i, 0, 0)),
            pl.BlockSpec((h, e), lambda i: (0, 0)),
            pl.BlockSpec((1, e), lambda i: (0, 0)),
            pl.BlockSpec((h, e), lambda i: (0, 0)),
            pl.BlockSpec((1, e), lambda i: (0, 0)),
            pl.BlockSpec((1, e), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, m, o), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, m, o), h_src.dtype),
        interpret=interpret,
        name="edge_score",
    )(h_src, h_dst, edge_feat, w_src, b_src.reshape(1, e), w_dst,
      w_feat.reshape(1, e), w_out.reshape(1, e), b_out.reshape(1, 1))
