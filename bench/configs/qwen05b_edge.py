"""Qwen1.5-0.5B with early exits: weights from the seed, plain reference.

The reference follows the published Qwen2 decoder (hf:Qwen/Qwen1.5-0.5B):
pre-norm blocks of RMSNorm, multi-head attention with q/k/v biases and
half-split rotary embeddings, and a SwiGLU feed-forward, with the LM head
tied to the embedding table. The system adds early exits: after layer
``e`` of ``exit_layers`` the hidden state goes through that exit's own
RMSNorm (the final norm for the last layer) into the shared head. Here
everything is float32 at the highest matmul precision over the whole
sequence at once, with no cache and no kernel, and it imports nothing of
the system under test.

``control_gaps`` is the same reference with its weights stored in fp8
(e4m3, one scale per output channel): the lower precision a change might
be tempted by, which the correctness check has to catch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def dims(cfg: dict):
    m = cfg["model"]
    d, h = m["hidden_size"], m["num_attention_heads"]
    return (d, h, m["num_key_value_heads"], d // h, m["intermediate_size"],
            m["vocab_size"], m["num_hidden_layers"])


def make_weights(cfg: dict, seed: int) -> dict:
    """All weights from ``seed`` in one jitted call on the device, bf16."""
    return _make_weights(_static(cfg), jnp.uint32(seed & 0xFFFFFFFF),
                         jnp.uint32((seed >> 32) & 0xFFFFFFFF))


def _static(cfg: dict):
    m = cfg["model"]
    return (dims(cfg), float(m["initializer_range"]),
            len(cfg["exit_layers"]))


@functools.partial(jax.jit, static_argnums=0)
def _make_weights(static, lo, hi):
    (d, h, kvh, hd, ff, vocab, n_layers), std, n_exits = static
    key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
    shapes = {
        "embed": (vocab, d),
        "ln1": (n_layers, d), "ln2": (n_layers, d),
        "wq": (n_layers, d, h * hd), "bq": (n_layers, h * hd),
        "wk": (n_layers, d, kvh * hd), "bk": (n_layers, kvh * hd),
        "wv": (n_layers, d, kvh * hd), "bv": (n_layers, kvh * hd),
        "wo": (n_layers, h * hd, d),
        "w_gate": (n_layers, d, ff), "w_up": (n_layers, d, ff),
        "w_down": (n_layers, ff, d),
        "exit_norm": (n_exits - 1, d), "final_norm": (d,),
    }
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes.items())):
        x = std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                    jnp.float32)
        if name in ("ln1", "ln2", "exit_norm", "final_norm"):
            x = 1.0 + x
        out[name] = x.astype(jnp.bfloat16)
    return out


# ------------------------------------------------------------------ reference
def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv            # [T, hd/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _fp8(w, axis):
    """Store ``w`` in fp8 e4m3 with one scale per slice along ``axis``."""
    scale = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _logits(static, w, tokens, exit_idx, fp8: bool):
    """Logits [B, T, V] at each row's exit, float32 throughout."""
    (d, h, kvh, hd, ff, vocab, n_layers), eps, theta, exits = static
    f32 = lambda a: a.astype(jnp.float32)
    b, t = tokens.shape
    pos = jnp.arange(t)
    embed = f32(w["embed"])
    if fp8:
        embed = _fp8(embed, axis=1)            # one scale per token row
    x = embed[tokens]
    causal = pos[None, :] <= pos[:, None]                       # [T, T]

    def layer(x, p):
        p = {k: f32(v) for k, v in p.items()}
        if fp8:
            p = {k: (_fp8(v, axis=0) if k in MATRICES else v)
                 for k, v in p.items()}
        y = _rms(x, p["ln1"], eps)
        q = (jnp.matmul(y, p["wq"], precision=HIGHEST) + p["bq"])
        k = (jnp.matmul(y, p["wk"], precision=HIGHEST) + p["bk"])
        v = (jnp.matmul(y, p["wv"], precision=HIGHEST) + p["bv"])
        q = _rope(q.reshape(b, t, h, hd), pos, theta)
        k = _rope(k.reshape(b, t, kvh, hd), pos, theta)
        v = v.reshape(b, t, kvh, hd)
        k = jnp.repeat(k, h // kvh, axis=2)
        v = jnp.repeat(v, h // kvh, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) / hd ** 0.5
        s = jnp.where(causal, s, -jnp.inf)
        a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v,
                       precision=HIGHEST).reshape(b, t, h * hd)
        x = x + jnp.matmul(a, p["wo"], precision=HIGHEST)
        y = _rms(x, p["ln2"], eps)
        g = jnp.matmul(y, p["w_gate"], precision=HIGHEST)
        u = jnp.matmul(y, p["w_up"], precision=HIGHEST)
        x = x + jnp.matmul(jax.nn.silu(g) * u, p["w_down"],
                           precision=HIGHEST)
        return x, x

    per_layer = {k: w[k] for k in ("ln1", "ln2", "wq", "bq", "wk", "bk", "wv",
                                   "bv", "wo", "w_gate", "w_up", "w_down")}
    _, hs = jax.lax.scan(layer, x, per_layer)                   # [L,B,T,D]
    depth = jnp.asarray(exits, jnp.int32)[exit_idx] - 1         # [B]
    hsel = hs[depth, jnp.arange(b)]                             # [B,T,D]
    norms = jnp.concatenate([f32(w["exit_norm"]),
                             f32(w["final_norm"])[None]], 0)
    hn = _rms(hsel, norms[exit_idx][:, None, :], eps)
    return jnp.einsum("btd,vd->btv", hn, embed, precision=HIGHEST)


def _gap(logits, tok):
    """How far the logit of ``tok`` lies below the best, in units of the
    logits' standard deviation at that position."""
    at = jnp.take_along_axis(logits, tok[..., None], -1)[..., 0]
    return (jnp.max(logits, -1) - at) / jnp.std(logits, -1)


def _ref_static(cfg: dict):
    m = cfg["model"]
    return (dims(cfg), float(m["rms_norm_eps"]), float(m["rope_theta"]),
            tuple(cfg["exit_layers"]))


@functools.partial(jax.jit, static_argnums=0)
def _served_gaps(static, w, tokens, exit_idx, served):
    logits = _logits(static, w, tokens, exit_idx, fp8=False)
    return _gap(logits, jnp.maximum(served, 0))


@functools.partial(jax.jit, static_argnums=0)
def _control_gaps(static, w, tokens, exit_idx):
    ref = _logits(static, w, tokens, exit_idx, fp8=False)
    low = _logits(static, w, tokens, exit_idx, fp8=True)
    return _gap(ref, jnp.argmax(low, -1))


def served_gaps(cfg: dict, weights: dict, tokens, exit_idx, served):
    """Gap of each served token under the reference.

    ``tokens`` [B, T] are the prompts followed by the served tokens (the
    input at each position), ``exit_idx`` [B] each row's exit as an index
    into ``exit_layers``, ``served`` [B, T] the token served at each
    position, -1 where nothing was served. Returns gaps [B, T]; those at
    positions where ``served`` is -1 mean nothing and are masked by the
    caller.
    """
    return _served_gaps(_ref_static(cfg), weights, tokens, exit_idx, served)


def control_gaps(cfg: dict, weights: dict, tokens, exit_idx):
    """Gap, under the reference, of the token the fp8 control puts first."""
    return _control_gaps(_ref_static(cfg), weights, tokens, exit_idx)
