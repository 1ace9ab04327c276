"""Smoke run of the system's two main paths on a TPU.

    python chip_smoke.py               # one chip: GRLE training + served decoding
    python chip_smoke.py --four-chips  # sweep pack sharded over 4 chips vs 1

One chip (the default) runs three phases through the entry points a user
calls:

1. device check: a TPU must be the default device, else exit 1;
2. GRLE training (Algorithm 1) at the paper's width, one scan episode
   of ``RolloutDriver.run(mode="scan")`` with the kernels left to pick
   the backend; the episode must hold compiled Pallas kernels, train to
   a finite loss, move the params and emit valid assignments, and the
   Pallas actor's logits must match the jnp reference's;
3. served decoding: ``EdgeServingEngine.serve_slot(decode=True)`` for
   Qwen1.5-0.5B at published widths (random weights from the seed); the
   final-exit decode step must agree with ``DecoderLM.forward_train``.

``--four-chips`` runs only the sharded path and its reference: a
4-method x 2-seed ``PackProgram`` with the cell axis on a 4-device
``fleet_mesh`` against the same pack on one chip.

This is a smoke run, not a benchmark: the rates it prints are
information. Its last stdout line is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``; any failed
check exits non-zero before that line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax                                           # noqa: E402
import jax.numpy as jnp                              # noqa: E402
import numpy as np                                   # noqa: E402

from repro.obs.compile import CompileTracker, use_compile_cache  # noqa: E402

# Pallas actor vs the jnp reference at highest precision. On TPU a
# default-precision f32 matmul takes bf16 passes (8 significant bits,
# relative rounding 2^-9), and the actor chains four matmul stages, so
# the logits may move by about 1% of their scale; a wrong kernel moves
# them by O(1).
ACTOR_TOL = 3e-2
# Decode step vs dense forward, both bf16 at highest precision: the two
# paths round the same bf16 activations at different points over 24
# layers.
DECODE_TOL = 5e-2
# Sharded vs one-chip pack: per-cell metrics, absolute on fractions and
# relative on reward.
PACK_TOL = 1e-3


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"[chip_smoke] FAILED: {what}")
    log(f"[check] ok: {what}")


def device_check():
    """Phase 1: the default device must be a TPU; returns the devices."""
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise SystemExit(f"[chip_smoke] no TPU: default device is "
                         f"{d0.platform} ({d0.device_kind})")
    from importlib.metadata import version
    log(f"[device] platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devs)} jax={jax.__version__} "
        f"libtpu={version('libtpu')}")
    return devs


def max_rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(1.0, np.max(np.abs(want))))


# ------------------------------------------------------------ GRLE training
def grle_phase(*, n_devices=14, n_fleets=64, n_slots=200, replay=128,
               batch=64, seed=0) -> dict:
    """Phase 2: one scan episode of GRLE training at the paper's width."""
    from repro.core import gcn
    from repro.core.graph import MECGraph
    from repro.core.policy import agent_def
    from repro.mec import MECConfig, MECEnv
    from repro.rollout import RolloutDriver

    env = MECEnv(MECConfig(n_devices=n_devices))
    adef = agent_def("grle", env, buffer_size=replay, batch_size=batch)
    drv = RolloutDriver(adef, n_fleets=n_fleets)
    k_agent, k_run, k_run2 = jax.random.split(jax.random.PRNGKey(seed), 3)
    state0 = adef.init(k_agent)
    log(f"[grle] M={env.M} N={env.N} L={env.L} hidden={adef.hidden} "
        f"fleets={n_fleets} slots={n_slots} replay={replay} batch={batch}")

    with CompileTracker() as ct:
        carry, trace = drv.run(k_run, n_slots, mode="scan",
                               agent_state=state0)
        jax.block_until_ready((carry, trace))
    t0 = time.perf_counter()
    jax.block_until_ready(drv.run(k_run2, n_slots, mode="scan",
                                  agent_state=state0))
    run_s = time.perf_counter() - t0
    log(f"[grle] info: compile {ct.total_compile_s:.3f} s, "
        f"{n_slots * n_fleets * env.M / run_s:.1f} device-slots/s "
        f"({n_slots / run_s:.1f} slots/s, warm run {run_s:.3f} s)")

    episode = drv._scan_cache[n_slots]
    text = episode.lower(drv.init_carry(k_run, agent_state=state0),
                         None).compile().as_text()
    n_kernels = text.count("tpu_custom_call")
    log(f"[grle] tpu_custom_call in compiled episode: {n_kernels}")
    check(n_kernels > 0, "episode runs compiled Pallas kernels")

    losses = np.asarray(trace.loss)
    losses = losses[~np.isnan(losses)]
    log(f"[grle] train steps {losses.size}, final loss "
        f"{float(losses[-1]) if losses.size else float('nan'):.6f}")
    check(losses.size > 0 and bool(np.all(np.isfinite(losses))),
          "training loss finite")
    moved = [not np.array_equal(np.asarray(a), np.asarray(b))
             for a, b in zip(jax.tree_util.tree_leaves(state0.params),
                             jax.tree_util.tree_leaves(
                                 carry.agent_state.params))]
    check(all(moved), "every param leaf changed by training")
    dec = np.asarray(trace.decisions)
    check(dec.shape == (n_slots, n_fleets, env.M)
          and dec.min() >= 0 and dec.max() < env.N * env.L,
          f"decisions are assignments in [0, {env.N * env.L})")

    # Pallas actor vs the jnp reference, on the graphs in the replay ring
    rp = carry.agent_state.replay
    graphs = MECGraph(rp.device_feat, rp.option_feat, rp.adj, rp.mask)
    params = carry.agent_state.params
    _, got = jax.jit(lambda p, g: gcn.apply(p, g, use_pallas=True))(
        params, graphs)
    with jax.default_matmul_precision("highest"):
        _, want = jax.jit(lambda p, g: gcn.apply(p, g, use_pallas=False))(
            params, graphs)
    valid = np.asarray(rp.mask) > 0.5
    err = max_rel_err(np.asarray(got)[valid], np.asarray(want)[valid])
    log(f"[grle] actor logits, Pallas vs reference on "
        f"{rp.adj.shape[0]} graphs: max |diff| / max(1, max |ref|) = "
        f"{err:.3e} (tolerance {ACTOR_TOL})")
    check(err <= ACTOR_TOL, "Pallas actor matches the jnp reference")
    return {"tpu_custom_call": n_kernels, "actor_err": err}


# ---------------------------------------------------------- served decoding
def serve_phase(cfg=None, *, n_slots=3, batch=4, prompt_len=8, max_new=4,
                seed=0) -> dict:
    """Phase 3: served early-exit decoding at published width."""
    from repro.configs import get_arch
    from repro.models.lm import DecoderLM
    from repro.serve import EdgeServingEngine, Replica, Request

    cfg = cfg or get_arch("qwen1_5_0_5b")
    t0 = time.perf_counter()
    engine = EdgeServingEngine(cfg, [Replica("a"), Replica("b", 0.5)],
                               batch_slots=batch, seed=seed)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(engine.params))
    log(f"[serve] {cfg.arch_id}: {cfg.n_layers} layers d={cfg.d_model} "
        f"vocab={cfg.vocab} exits={cfg.exit_layers} params={n_params} "
        f"({cfg.dtype}), init {time.perf_counter() - t0:.3f} s")

    rng = np.random.default_rng(seed)
    n_tokens = 0
    t0 = time.perf_counter()
    for slot in range(n_slots):
        reqs = [Request(tokens=rng.integers(0, cfg.vocab, size=prompt_len,
                                            dtype=np.int32),
                        deadline_s=0.05, max_new=max_new)
                for _ in range(batch)]
        assignments, info = engine.serve_slot(reqs, decode=True)
        texts = info["texts"]
        log(f"[serve] slot {slot}: "
            + ", ".join(f"{r}@exit{e}" for r, e in assignments)
            + f" -> {texts}")
        check(len(texts) == batch
              and all(len(t) == max_new for t in texts)
              and all(0 <= tok < cfg.vocab for t in texts for tok in t),
              f"slot {slot}: every request got {max_new} tokens in "
              f"[0, {cfg.vocab})")
        n_tokens += sum(len(t) for t in texts)
    log(f"[serve] info: {n_slots} slots, {n_tokens} tokens in "
        f"{time.perf_counter() - t0:.3f} s (compiles included)")

    # final-exit decode step vs the dense forward on the same tokens
    toks = jnp.asarray(rng.integers(0, cfg.vocab, size=(batch, prompt_len),
                                    dtype=np.int32))
    step = engine._steps[cfg.n_layers]
    with jax.default_matmul_precision("highest"):
        hid, _ = jax.jit(lambda p, t: DecoderLM.forward_train(p, cfg, t))(
            engine.params, toks)
        dense = jax.jit(DecoderLM.logits)(engine.params, hid[cfg.n_layers])
        cache = engine.model.init_cache(cfg, batch, engine.cache_len)
        steps = []
        for pos in range(prompt_len):
            logits, cache = step(engine.params, cache, toks[:, pos],
                                 jnp.full((batch,), pos, jnp.int32))
            steps.append(logits)
    stepped = jnp.stack(steps, axis=1)
    err = max_rel_err(stepped, dense)
    agree = float(np.mean(np.asarray(jnp.argmax(stepped, -1))
                          == np.asarray(jnp.argmax(dense, -1))))
    log(f"[serve] exit {cfg.n_layers} decode vs forward_train over "
        f"{batch}x{prompt_len} prompt positions: max |diff| / "
        f"max(1, max |ref|) = {err:.3e} (tolerance {DECODE_TOL}), "
        f"argmax agreement {agree:.4f}")
    check(err <= DECODE_TOL, "decode step matches forward_train")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"[serve] peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
    return {"decode_err": err, "argmax_agree": agree}


# ---------------------------------------------------------- four-chip pack
PACK_KEYS = ("ssp", "avg_accuracy", "deadline_miss", "avg_reward", "tasks",
             "train_steps")


def pack_phase(devs, *, n_devices=14, n_slots=100, replay=128, batch=64,
               seeds=2) -> dict:
    """Four chips: the cell axis sharded over 4 devices vs one chip."""
    from repro.sharding.fleet import fleet_mesh
    from repro.sweep import SweepSpec, pack_cells
    from repro.sweep.runner import PackProgram

    mesh = fleet_mesh(4)
    check(mesh is not None and mesh.devices.size == 4, "4-device fleet mesh")
    spec = SweepSpec.from_names("fig5_baseline", "grle,grl,drooe,droo",
                                seeds, n_devices=n_devices, n_slots=n_slots,
                                replay_capacity=replay, batch_size=batch,
                                train_every=10)
    worst = 0.0
    for pack in pack_cells(spec.expand()):
        sharded = PackProgram(pack, mesh=mesh)
        placed = set()
        for leaf in jax.tree_util.tree_leaves(sharded._carries):
            shards = leaf.addressable_shards
            placed |= {s.device for s in shards}
            if len(shards) != 4 or {s.data.shape[0] for s in shards} != {
                    len(pack.cells) // 4}:
                check(False, f"{pack.label()}: leaf {leaf.shape} split "
                             f"over the 4 devices")
        log(f"[pack] {pack.label()}: {len(pack.cells)} cells, cell axis on "
            f"devices {sorted(d.id for d in placed)}")
        check(len(placed) == 4, f"{pack.label()}: cell axis on 4 distinct "
                                f"devices")
        t0 = time.perf_counter()
        rows_sh = sharded.run()
        t_sh = time.perf_counter() - t0
        with jax.default_device(devs[0]):
            single = PackProgram(pack, mesh=None)
        check({d for leaf in jax.tree_util.tree_leaves(single._carries)
               for d in leaf.devices()} == {devs[0]},
              f"{pack.label()}: reference pack on one chip")
        with jax.default_device(devs[0]):
            t0 = time.perf_counter()
            rows_1 = single.run()
            t_1 = time.perf_counter() - t0
        log(f"[pack] info: first run (compile included) sharded {t_sh:.3f} "
            f"s, one chip {t_1:.3f} s")
        for cell, a, b in zip(pack.cells, rows_sh, rows_1):
            diffs = {}
            for k in PACK_KEYS:
                scale = max(1.0, abs(b[k])) if k in ("avg_reward", "tasks",
                                                     "train_steps") else 1.0
                diffs[k] = abs(a[k] - b[k]) / scale
            worst = max(worst, max(diffs.values()))
            log(f"[pack] {cell.method}/s{cell.seed}: sharded "
                + " ".join(f"{k}={a[k]:.6g}" for k in PACK_KEYS)
                + " | one chip "
                + " ".join(f"{k}={b[k]:.6g}" for k in PACK_KEYS))
            check(max(diffs.values()) <= PACK_TOL,
                  f"{cell.method}/s{cell.seed}: sharded == one chip "
                  f"(worst {max(diffs.values()):.3e}, tolerance {PACK_TOL})")
    return {"pack_worst": worst}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the cell axis sharded over 4 chips and "
                         "its one-chip reference")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    use_compile_cache()
    devs = device_check()
    d0 = devs[0]
    if args.four_chips:
        if len(devs) < 4:
            raise SystemExit(f"[chip_smoke] --four-chips needs 4 devices, "
                             f"found {len(devs)}")
        pack_phase(devs)
    else:
        # fleet_mesh() takes every visible device: pin both phases to one
        with jax.default_device(d0):
            grle_phase(seed=args.seed)
            serve_phase(seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
