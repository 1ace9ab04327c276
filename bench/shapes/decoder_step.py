"""Operations and bytes of one decoder position, from the shapes alone.

For a dense pre-norm decoder with grouped-query attention, a SwiGLU
feed-forward and an LM head over the whole vocabulary, run to an early
exit after ``exit_layer`` layers. ``kv_len`` is the number of cached
positions the new token attends to, itself included. Multiply-adds
count as 2 operations; norms, RoPE, softmax and the residual adds are
left out (they are under 0.1% of the total at published widths).
"""
from __future__ import annotations


def _dims(cfg: dict):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kvh = cfg["num_key_value_heads"]
    hd = d // h
    return d, h, kvh, hd, cfg["intermediate_size"], cfg["vocab_size"]


def layer_weights(cfg: dict) -> int:
    """Weights of one layer that a token multiplies through."""
    d, h, kvh, hd, ff, _ = _dims(cfg)
    return d * h * hd + 2 * d * kvh * hd + h * hd * d + 3 * d * ff


def flops(cfg: dict, exit_layer: int, kv_len: int) -> float:
    """Operations for one token at one position."""
    d, h, kvh, hd, ff, vocab = _dims(cfg)
    per_layer = 2 * layer_weights(cfg) + 2 * 2 * h * hd * kv_len
    return float(exit_layer * per_layer + 2 * d * vocab)


def step_bytes(cfg: dict, exit_layer: int, kv_lens, *,
               weight_bytes: int = 2, cache_bytes: int = 2) -> float:
    """Bytes one decode step of a batch must read from memory.

    The weights of ``exit_layer`` layers, the LM head, and the keys and
    values each row attends to: ``kv_lens`` holds one length per row.
    """
    d, h, kvh, hd, ff, vocab = _dims(cfg)
    weights = exit_layer * layer_weights(cfg) + d * vocab
    kv = exit_layer * 2 * kvh * hd * sum(int(n) for n in kv_lens)
    return float(weights * weight_bytes + kv * cache_bytes)
