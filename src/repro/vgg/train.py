"""Training + exit profiling for multi-exit VGG-16 (paper §VI-B).

The paper first trains the main branch on CIFAR-10, then trains the exit
classifiers on top of the pretrained backbone. We follow the same two-stage
recipe on the synthetic image task:

  stage 1: backbone + main head, cross-entropy on exit 17;
  stage 2: exit heads only (backbone frozen via stop_gradient), summed CE.

``profile_exits`` then reproduces a Table-I-shaped table: per-exit accuracy
on held-out data + per-exit latency (measured CPU ms and analytic TPU-v5e
roofline ms).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.data import SyntheticImages
from repro.obs.peaks import TPU_V5E, chip_peaks
from repro.optim import adam
from repro.optim.optimizers import apply_updates
from repro.vgg.model import N_EXITS, VGG16EE


def _ce(logits, labels):
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], -1))


def train_vgg_ee(key, *, width_mult: float = 0.25, steps_main: int = 300,
                 steps_exits: int = 300, batch: int = 64, lr: float = 1e-3,
                 noise: float = 0.8, log_every: int = 0):
    """Two-stage training; returns (params, history dict)."""
    kinit, kdata = jax.random.split(key)
    params = VGG16EE.init(kinit, width_mult=width_mult)
    data = SyntheticImages(noise=noise)
    opt = adam(lr)

    # ---------------------------------------------------------- stage 1: main
    def loss_main(p, images, labels):
        outs = VGG16EE.apply(p, images, up_to_exit=N_EXITS)
        return _ce(outs[N_EXITS], labels)

    @jax.jit
    def step_main(p, s, images, labels):
        l, g = jax.value_and_grad(loss_main)(p, images, labels)
        upd, s = opt.update(g, s, p)
        return apply_updates(p, upd), s, l

    # ------------------------------------------------- stage 2: frozen trunk
    def loss_exits(p_exits, p_frozen, images, labels):
        p = dict(p_frozen)
        p["exits"] = p_exits
        p = {**p, "stages": jax.tree_util.tree_map(jax.lax.stop_gradient,
                                                   p["stages"])}
        outs = VGG16EE.apply(p, images, up_to_exit=N_EXITS)
        losses = [_ce(v, labels) for k, v in outs.items() if k != N_EXITS]
        return sum(losses) / max(len(losses), 1)

    @jax.jit
    def step_exits(p_exits, p_frozen, s, images, labels):
        l, g = jax.value_and_grad(loss_exits)(p_exits, p_frozen, images, labels)
        upd, s = opt.update(g, s, p_exits)
        return apply_updates(p_exits, upd), s, l

    hist = {"main_loss": [], "exit_loss": []}
    state = opt.init(params)
    for i in range(steps_main):
        kdata, kb = jax.random.split(kdata)
        images, labels = data.sample(kb, batch)
        params, state, l = step_main(params, state, images, labels)
        hist["main_loss"].append(float(l))
        if log_every and i % log_every == 0:
            print(f"[vgg stage1] step {i} loss {float(l):.3f}")

    p_exits = params["exits"]
    state = opt.init(p_exits)
    for i in range(steps_exits):
        kdata, kb = jax.random.split(kdata)
        images, labels = data.sample(kb, batch)
        p_exits, state, l = step_exits(p_exits, params, state, images, labels)
        hist["exit_loss"].append(float(l))
        if log_every and i % log_every == 0:
            print(f"[vgg stage2] step {i} loss {float(l):.3f}")
    params["exits"] = p_exits
    return params, hist


def profile_exits(params, *, width_mult: float = 0.25, eval_batches: int = 20,
                  batch: int = 256, noise: float = 0.8, data_seed: int = 0,
                  eval_seed: int = 10_000,
                  candidate_exits=(1, 3, 4, 7, 17), measure_ms: bool = True):
    """Accuracy + latency per candidate exit (the paper's Table I analogue).

    Uses the *same* synthetic task (``data_seed`` fixes the class
    prototypes) but fresh sampling keys — a held-out eval split.
    """
    data = SyntheticImages(noise=noise, seed=data_seed)
    key = jax.random.PRNGKey(eval_seed)
    acc = {e: 0.0 for e in candidate_exits}
    n = 0
    fwd = {e: jax.jit(lambda p, x, e=e: VGG16EE.apply(p, x, up_to_exit=e))
           for e in candidate_exits}
    for _ in range(eval_batches):
        key, kb = jax.random.split(key)
        images, labels = data.sample(kb, batch)
        for e in candidate_exits:
            outs = fwd[e](params, images)
            pred = jnp.argmax(outs[max(outs)], -1)
            acc[e] += float(jnp.sum(pred == labels))
        n += batch

    flops = VGG16EE.exit_flops(width_mult)
    v5e = chip_peaks(TPU_V5E)
    rows = []
    for e in candidate_exits:
        row = {"exit": e, "accuracy": acc[e] / n, "gflops": flops[e]}
        if measure_ms:
            key, kb = jax.random.split(key)
            img1, _ = data.sample(kb, 1)
            fwd[e](params, img1)  # warmup
            t0 = time.perf_counter()
            for _ in range(10):
                jax.block_until_ready(fwd[e](params, img1))
            row["cpu_ms"] = (time.perf_counter() - t0) * 100.0
        # analytic TPU-v5e roofline latency from the published peaks
        t_comp = flops[e] * 1e9 / (v5e.flops_bf16 * 0.15)
        t_mem = flops[e] * 1e9 * 0.05 / v5e.hbm_bw  # ~bytes ≈ 5% of FLOPs
        row["tpu_v5e_ms"] = (max(t_comp, t_mem) + 50e-6) * 1e3
        rows.append(row)
    return rows
