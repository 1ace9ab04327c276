"""Layout ``qwen2``: the Qwen2 decoder (dense SwiGLU blocks, multi-head
attention with q/k/v biases, a tied LM head) in the program's terms.

A serving configuration names its layout (``"layout": "qwen2"``) and the
serve driver loads this file by that name. A layout gives two functions:
``arch_config(cfg)``, the program's ``ArchConfig`` for the
configuration's ``model`` sizes, and ``program_params(w, arch)``, the
reference's weights (``make_weights`` beside the configuration) in the
engine's parameter tree.
"""
from __future__ import annotations


def arch_config(cfg: dict):
    from repro.models.config import ArchConfig
    m = cfg["model"]
    return ArchConfig(
        arch_id=cfg["name"], family="dense",
        n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        d_ff=m["intermediate_size"], vocab=m["vocab_size"],
        attn_kind="gqa", n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], qkv_bias=True,
        rope_theta=float(m["rope_theta"]), norm_eps=float(m["rms_norm_eps"]),
        exit_layers=tuple(cfg["exit_layers"]),
        dtype={"bfloat16": "bfloat16", "float32": "float32"}[m["torch_dtype"]])


def program_params(w: dict, arch):
    """The benchmark's weights in the engine's parameter layout.

    Checked leaf by leaf against the layout the model's own ``init``
    would build, so a change of layout fails here and not in silence.
    """
    import jax
    import jax.numpy as jnp
    from repro.models.lm import model_for
    p = {
        "embed": {"table": w["embed"]},
        "blocks": {
            "ln1": {"scale": w["ln1"]},
            "attn": {"wq": {"w": w["wq"], "b": w["bq"]},
                     "wk": {"w": w["wk"], "b": w["bk"]},
                     "wv": {"w": w["wv"], "b": w["bv"]},
                     "wo": {"w": w["wo"]}},
            "ln2": {"scale": w["ln2"]},
            "ffn": {"w1": {"w": w["w_gate"]}, "w3": {"w": w["w_up"]},
                    "w2": {"w": w["w_down"]}},
        },
        "final_norm": {"scale": w["final_norm"]},
        "lm_head": {"w": jax.jit(jnp.transpose)(w["embed"])},   # tied head
        "exit_norms": {"scale": jnp.concatenate(
            [w["exit_norm"], w["final_norm"][None]], 0)},
    }
    want = jax.eval_shape(lambda k: model_for(arch).init(k, arch),
                          jax.random.PRNGKey(0))
    got = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), p)
    exp = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), want)
    if got != exp:
        raise ValueError(f"engine parameter layout changed:\n{exp}\n"
                         f"!=\n{got}")
    return p
