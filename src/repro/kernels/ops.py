"""Jit'd public wrappers for the Pallas kernels.

On TPU these dispatch to the compiled kernels; elsewhere they use the jnp
references, and ``use_pallas=True`` runs the kernel bodies in interpret
mode for validation (each kernel picks compiled or interpreted from the
backend). The model code keeps its jnp paths as the dry-run lowering
target (Pallas does not lower on the CPU backend).

``gcn_agg`` and ``edge_score`` — the actor-path kernels the training
loss differentiates through — carry hand-written VJPs here: Pallas
calls are not auto-differentiable, and the custom backward is also what
makes the CPU path fast (the edge scorer's [B, M, O, E] hidden is
recomputed inside each fused reduction instead of being stored and
re-read). The backward rules return cotangents for every operand;
consumers that never differentiate w.r.t. an operand (e.g. the replay
graphs' adjacency in the Eq-16 loss) get those branches removed by XLA
dead-code elimination.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.decode_attention import decode_attention as _decode
from repro.kernels.edge_score import edge_score as _edge
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.gcn_agg import gcn_agg as _gcn
from repro.kernels.ssm_scan import ssm_scan as _ssm

_EPS = 1e-6


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def flash_attention(q, k, v, *, causal=True, window=None, block_q=128,
                    block_k=128, use_pallas=None):
    use = _on_tpu() if use_pallas is None else use_pallas
    if use:
        return _flash(q, k, v, causal=causal, window=window, block_q=block_q,
                      block_k=block_k)
    return _ref.flash_attention_ref(q, k, v, causal=causal, window=window)


def decode_attention(q, k, v, lengths, *, block_k=256, use_pallas=None):
    use = _on_tpu() if use_pallas is None else use_pallas
    if use:
        return _decode(q, k, v, lengths, block_k=block_k)
    return _ref.decode_attention_ref(q, k, v, lengths)


def ssm_scan(q, k, v, log_w, bonus_u=None, *, chunk=128, use_pallas=None):
    use = _on_tpu() if use_pallas is None else use_pallas
    if use:
        return _ssm(q, k, v, log_w, bonus_u, chunk=chunk)
    y, _ = _ref.ssm_scan_ref(q, k, v, log_w, bonus_u=bonus_u)
    return y


def _flat2(x):
    """[B, N, F] -> [B*N, F] so weight grads are single clean GEMMs."""
    return x.reshape(-1, x.shape[-1])


# ---------------------------------------------------------------- gcn_agg
@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _gcn_agg(adj, self_feat, nbr_feat, w_self, w_nbr, bias, use):
    if use:
        return _gcn(adj, self_feat, nbr_feat, w_self, w_nbr, bias)
    return _ref.gcn_agg_ref(adj, self_feat, nbr_feat, w_self, w_nbr, bias)


def _gcn_agg_fwd(adj, self_feat, nbr_feat, w_self, w_nbr, bias, use):
    out = _gcn_agg(adj, self_feat, nbr_feat, w_self, w_nbr, bias, use)
    return out, (adj, self_feat, nbr_feat, w_self, w_nbr, out)


def _gcn_agg_bwd(use, res, dout):
    """VJP of relu(hs @ ws + agg @ wn + b), agg = (adj @ hn)/(deg + eps).

    The relu mask comes from the saved primal output (out > 0 iff the
    pre-activation was positive); ``agg`` is recomputed — one batched
    matmul — instead of stored.
    """
    adj, hs, hn, ws, wn, out = res
    deg = adj.sum(-1, keepdims=True) + _EPS
    agg = (adj @ hn) / deg
    dpre = jnp.where(out > 0, dout, 0.0)              # [B, M, H]
    dbias = dpre.sum(axis=(0, 1))
    dws = _flat2(hs).T @ _flat2(dpre)
    dwn = _flat2(agg).T @ _flat2(dpre)
    dhs = dpre @ ws.T
    dagg_n = (dpre @ wn.T) / deg                      # dagg / deg, [B, M, Fn]
    dhn = jnp.swapaxes(adj, -1, -2) @ dagg_n
    # d(agg)/d(adj[i, o]) = (hn[o] - agg[i]) / deg[i]
    dadj = dagg_n @ jnp.swapaxes(hn, -1, -2) \
        - (dagg_n * agg).sum(-1, keepdims=True)
    return dadj, dhs, dhn, dws, dwn, dbias


_gcn_agg.defvjp(_gcn_agg_fwd, _gcn_agg_bwd)


def gcn_agg(adj, self_feat, nbr_feat, w_self, w_nbr, bias, *,
            use_pallas=None):
    """Eq-12 message passing: relu(self @ w_self + agg @ w_nbr + bias).

    adj [B, M, O], self_feat [B, M, Fs], nbr_feat [B, O, Fn] ->
    [B, M, H]. Differentiable (hand-written VJP, shared by both
    backends).
    """
    use = _on_tpu() if use_pallas is None else use_pallas
    return _gcn_agg(adj, self_feat, nbr_feat, w_self, w_nbr, bias, use)


# ------------------------------------------------------------- edge_score
@functools.partial(jax.custom_vjp, nondiff_argnums=(9,))
def _edge_score(h_src, h_dst, ef, w_src, b_src, w_dst, w_feat, w_out,
                b_out, use):
    if use:
        return _edge(h_src, h_dst, ef, w_src, b_src, w_dst, w_feat,
                     w_out, b_out)
    return _ref.edge_score_ref(h_src, h_dst, ef, w_src, b_src, w_dst,
                               w_feat, w_out, b_out)


def _edge_score_fwd(h_src, h_dst, ef, w_src, b_src, w_dst, w_feat, w_out,
                    b_out, use):
    out = _edge_score(h_src, h_dst, ef, w_src, b_src, w_dst, w_feat,
                      w_out, b_out, use)
    return out, (h_src, h_dst, ef, w_src, b_src, w_dst, w_feat, w_out)


def _edge_score_bwd(use, res, dl):
    """VJP of sum_e relu(src + dst + ef*wf)_e * wo_e + bo.

    The [B, M, O, E] hidden is recomputed *inside each reduction* (the
    thunks below) rather than materialized once and re-read — on a
    bandwidth-bound host every fused recompute-reduce touches only the
    small src/dst/ef operands.
    """
    h_src, h_dst, ef, w_src, b_src, w_dst, w_feat, w_out = res
    src = h_src @ w_src + b_src                       # [B, M, E]
    dst = h_dst @ w_dst                               # [B, O, E]

    def x():
        return (src[..., :, None, :] + dst[..., None, :, :]
                + ef[..., None] * w_feat)

    def am():                                         # dL/dx, masked
        return jnp.where(x() > 0, dl[..., None] * w_out, 0.0)

    dsrc = am().sum(-2)                               # [B, M, E]
    ddst = am().sum(-3)                               # [B, O, E]
    d_ef = (am() * w_feat).sum(-1)                    # [B, M, O]
    dwf = (am() * ef[..., None]).sum(axis=(0, 1, 2))  # [E]
    dwo = (jnp.maximum(x(), 0.0) * dl[..., None]).sum(axis=(0, 1, 2))
    dbo = dl.sum()[None]
    dh_src = dsrc @ w_src.T
    dh_dst = ddst @ w_dst.T
    dw_src = _flat2(h_src).T @ _flat2(dsrc)
    dw_dst = _flat2(h_dst).T @ _flat2(ddst)
    db_src = dsrc.sum(axis=(0, 1))
    return (dh_src, dh_dst, d_ef, dw_src, db_src, dw_dst, dwf, dwo, dbo)


_edge_score.defvjp(_edge_score_fwd, _edge_score_bwd)


def edge_score(h_src, h_dst, edge_feat, w_src, b_src, w_dst, w_feat,
               w_out, b_out, *, use_pallas=None):
    """Eq-13/14 fused edge scorer: per-edge MLP logits [B, M, O].

    h_src [B, M, H], h_dst [B, O, H], edge_feat [B, M, O];
    w_src/w_dst [H, E], b_src/w_feat/w_out [E], b_out [1].
    Differentiable (hand-written VJP, shared by both backends).
    """
    use = _on_tpu() if use_pallas is None else use_pallas
    return _edge_score(h_src, h_dst, edge_feat, w_src, b_src, w_dst,
                       w_feat, w_out, b_out, use)
