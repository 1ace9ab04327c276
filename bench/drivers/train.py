"""Driver ``train``: the paper's training job, GRLE's Algorithm 1 over
fleets of MEC networks sharing one learner.

The system under test is ``RolloutDriver.run(key, n_slots, mode="scan",
agent_state=...)``: one compiled episode in which every fleet draws its
tasks, the actor and critic decide, the world steps, and the learner
trains every ``train_every`` slots. The window chains episodes as users
chain them: each starts from the previous one's final agent state
(weights and Adam's moments carry over; the replay ring and slot counter
start afresh), and each episode's losses are read back, as a training
log reads them. ``train_slots_per_s`` is fleets x slots of the episodes
completed over the time to the last completion.

Correctness: the plain reference beside the configuration replays the
window's first episode, from the seed's fresh agent, with the program's
decisions, and compares each slot's decisions, rewards and train losses
(``decision_gap``, ``reward_gap``, ``loss_gap``) and, from that
episode's final carry after its 20 train steps, Adam's first moment
(``moment_gap``) and the weights' change (``change_gap``), each leaf by
its norm. No number compares Adam's update coordinate by coordinate:
its first step is lr * sign(g), a step function of the gradient.
"""
from __future__ import annotations

import functools
import gc
import json
import os
import shutil
import time

import numpy as np

from bench.shapes import grle

TRACE_SECONDS = 3.0     # traced part of the window in a --trace 1 run
QUIET_LEAF = 1e-3       # leaves whose first gradient is under this share
#                         of the median leaf's move by round-off alone


def mec_config(cfg: dict):
    from repro.mec import MECConfig
    w = cfg["mec"]
    times = np.asarray(w["exit_ms"], np.float64) * 1e-3
    return MECConfig(
        n_devices=w["n_devices"], n_servers=w["n_servers"],
        exit_times_s=tuple(map(tuple, times.tolist())),
        exit_accuracy=tuple(w["exit_accuracy"]),
        slot_s=w["slot_s"], deadline_s=w["deadline_s"],
        task_kbytes=tuple(w["task_kbytes"]), rate_mbps=tuple(w["rate_mbps"]),
        capacity_range=(w["capacity"], w["capacity"]),
        inference_jitter=w["inference_jitter"], csi_error=w["csi_error"],
        connectivity_drop=w["link_drop"], workload="iid")


def agent(cfg: dict, env):
    from repro.core.policy import agent_def
    a = cfg["agent"]
    if tuple(a["adam"]) != (0.9, 0.999, 1e-8):
        raise ValueError("the program's Adam has b1 0.9, b2 0.999, eps 1e-8")
    return agent_def(a["method"], env, hidden=tuple(a["hidden"]),
                     n_candidates=a["candidates"], n_random=a["n_random"],
                     buffer_size=a["replay"], batch_size=a["minibatch"],
                     train_every=a["train_every"], lr=a["lr"])


@functools.lru_cache(maxsize=1)
def rollout(cfg_json: str, n_fleets: int):
    """The system under test for a configuration (as JSON): its
    ``AgentDef`` and its ``RolloutDriver`` over ``n_fleets`` fleets. Kept
    for the process, so that a second run in it (``bench.control``'s
    seeds, the tests) reuses the compiled episode."""
    from repro.mec import MECEnv
    from repro.rollout import RolloutDriver
    cfg = json.loads(cfg_json)
    adef = agent(cfg, MECEnv(mec_config(cfg)))
    return adef, RolloutDriver(adef, n_fleets=n_fleets)


def flat(tree, prefix: str = "") -> dict:
    """Nested dict of leaves -> {"dev1/w": leaf}."""
    if isinstance(tree, dict):
        out = {}
        for k, v in tree.items():
            out.update(flat(v, f"{prefix}{k}/"))
        return out
    return {prefix[:-1]: tree}


def check_layout(params, want) -> None:
    """The program's actor weights have the reference's names and shapes,
    so a change of layout fails here and not in silence."""
    got = {k: v.shape for k, v in flat(params).items()}
    exp = {k: v.shape for k, v in flat(want).items()}
    if got != exp:
        raise ValueError(f"actor weight layout changed:\n{exp}\n!=\n{got}")


def norm(x) -> float:
    return float(np.linalg.norm(np.asarray(x, np.float64)))


def norm_gap(got: dict, want: dict, leaves) -> float:
    """Worst leaf's gap between the two norms, over the larger of the
    reference's norm of that leaf and of the median leaf."""
    g = {k: norm(got[k]) for k in leaves}
    w = {k: norm(want[k]) for k in leaves}
    med = float(np.median(list(w.values())))
    return max(abs(g[k] - w[k]) / max(w[k], med) for k in leaves)


def episode_ok(cfg: dict, n_fleets: int, loss) -> bool:
    """A loss at every due slot and at no other."""
    due = grle.due_slots(cfg, n_fleets, len(loss))
    return bool(np.all(np.isfinite(loss[due]))
                and np.all(np.isnan(loss[~due])))


def readings(ref, cfg: dict, n_fleets: int, seed: int, ep: dict) -> dict:
    """The check's numbers for a subject's episode 0 of ``seed``:
    ``decisions``, ``reward``, ``loss`` of every slot, and from its final
    state the weights ``params``, Adam's first moment ``mu`` and the
    weights it started from, ``p0``."""
    k_agent, k_eps = ref.keys(seed)
    p0 = ref.make_weights(cfg, k_agent)
    rep = ref.reference(cfg, n_fleets).run(
        ref.episode_key(k_eps, 0), p0, len(ep["decisions"]),
        follow=ep["decisions"])
    decision_gap = float(np.mean(rep["decisions"] != ep["decisions"]))
    want = np.asarray(rep["reward"], np.float64)
    reward_gap = float(np.max(np.abs(np.asarray(ep["reward"]) - want))
                       / np.mean(np.abs(want)))
    lp, lr = np.asarray(ep["loss"], np.float64), rep["loss"]
    both = ~np.isnan(lp) & ~np.isnan(lr)
    # a step taken on one side only counts as a gap of 1
    loss_gap = max([0.0] + [1.0] * int(np.sum(np.isnan(lp) != np.isnan(lr)))
                   + list(np.abs(lp[both] - lr[both]) / np.abs(lr[both])))
    g1 = {k: norm(v) for k, v in flat(rep["g1"]).items()}
    med = float(np.median(list(g1.values())))
    moved = sorted(k for k, v in g1.items() if v >= QUIET_LEAF * med)
    moment_gap = norm_gap(flat(ep["mu"]), flat(rep["mu"]), moved)
    p0_ref, p0_sub = flat(host(p0)), flat(ep["p0"])
    dp = {k: np.asarray(v, np.float64) - p0_sub[k]
          for k, v in flat(ep["params"]).items()}
    dp_ref = {k: np.asarray(v, np.float64) - p0_ref[k]
              for k, v in flat(rep["params"]).items()}
    change_gap = norm_gap(dp, dp_ref, moved)
    return dict(decision_gap=decision_gap, reward_gap=reward_gap,
                loss_gap=float(loss_gap), moment_gap=moment_gap,
                change_gap=change_gap)


class Window:
    """Chains episodes of ``drv`` from ``state``: episode i runs from
    ``key(i)`` and the previous episode's final agent state. Records
    each completion's seconds from window start and whether its losses
    came out whole."""

    def __init__(self, drv, key, n_slots: int, state, ok):
        self.drv, self.key, self.n_slots = drv, key, n_slots
        self.state, self.ok = state, ok
        self.done = []      # (seconds from start, losses whole)
        self.first = None   # the first episode's (trace, final agent state)
        self.t0 = time.perf_counter()

    def until(self, t_stop: float) -> None:
        import jax
        while time.perf_counter() - self.t0 < t_stop:
            i = len(self.done)
            with jax.profiler.TraceAnnotation("bench/episode"):
                carry, trace = self.drv.run(self.key(i), self.n_slots,
                                            mode="scan",
                                            agent_state=self.state)
                loss = np.asarray(trace.loss)
            if i == 0:
                self.first = trace, carry.agent_state
            self.state = carry.agent_state
            self.done.append((time.perf_counter() - self.t0, self.ok(loss)))


def host(tree):
    import jax
    return jax.tree_util.tree_map(np.asarray, tree)


def run(ctx: dict) -> dict:
    import jax
    from bench.drivers.serve import Compiles, log

    cfg, mix, ref = ctx["config"], ctx["traffic"], ctx["reference"]
    seed, seconds = ctx["seed"], ctx["seconds"]
    compiles = Compiles()
    compiles.on = True

    n_fleets, n_slots = int(mix["fleets"]), int(mix["episode_slots"])
    adef, drv = rollout(json.dumps(cfg, sort_keys=True), n_fleets)
    k_agent, k_eps = ref.keys(seed)

    def key(i):
        return ref.episode_key(k_eps, i)

    state0 = adef.init(k_agent)
    check_layout(state0.params,
                 jax.eval_shape(lambda k: ref.make_weights(cfg, k), k_agent))
    # every program the window runs: an episode from the fresh agent and
    # one from a trained agent
    c, t = drv.run(key(0), n_slots, mode="scan", agent_state=state0)
    c, t = drv.run(key(1), n_slots, mode="scan", agent_state=c.agent_state)
    np.asarray(t.loss)
    del c, t
    setup_compiles = compiles.take()
    setup_s = time.time() - ctx["t_process"]
    log(f"set-up {setup_s:.3f} s, compiles {setup_compiles}")

    # ------------------------------------------------------------ window
    win = Window(drv, key, n_slots, state0,
                 lambda loss: episode_ok(cfg, n_fleets, loss))
    traced = None
    if ctx["trace"]:
        tdir = ctx["trace_dir"]
        shutil.rmtree(tdir, ignore_errors=True)
        os.makedirs(tdir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(tdir, profiler_options=opts)
        win.t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench/window"):
            win.until(min(TRACE_SECONDS, seconds))
        traced = len(win.done)
        jax.profiler.stop_trace()
    else:
        win.t0 = time.perf_counter()
    win.until(seconds)
    in_window = compiles.take()
    compiles.close()
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    episodes = len(win.done)
    log(f"window {win.done[-1][0]:.3f} s, {episodes} episodes, "
        f"compiles in window {in_window}")

    trace, last = win.first
    episode = dict(decisions=np.asarray(trace.decisions),
                   reward=np.asarray(trace.reward),
                   loss=np.asarray(trace.loss), p0=host(state0.params),
                   params=host(last.params), mu=host(last.opt_state["mu"]))
    fleet_slots = n_fleets * n_slots
    bad = sum(1 for _, ok in win.done if not ok)
    dec = episode["decisions"]
    if dec.min() < 0 or dec.max() >= adef.env.N * adef.env.L:
        bad += 1
    metrics = {"train_slots_per_s": episodes * fleet_slots / win.done[-1][0],
               "setup_s": setup_s}

    # ----------------------------------------------- after the window
    layer_ctx = dict(setup_compile_s=setup_compiles["seconds"])
    if traced is not None:
        from bench import trace as tr
        layer_ctx.update(events=tr.load(ctx["trace_dir"]), episodes=traced,
                         config=cfg, traffic=mix, peaks=ctx["peaks"])
    del drv, win, trace, last, state0
    gc.collect()

    got = readings(ref, cfg, n_fleets, seed, episode)
    checks = {k: [v, float(cfg["limits"][k])] for k, v in got.items()}
    checks["bad_episodes"] = [bad, 0]
    correct = all(v <= lim for v, lim in checks.values())
    return dict(correct=correct, attempted=episodes * fleet_slots,
                failed=bad * fleet_slots, metrics=metrics,
                memory_peak_bytes=peak, compiles_in_window=in_window,
                setup_compiles=setup_compiles, checks=checks,
                layer_ctx=layer_ctx,
                checked_tokens=int(dec.size))   # decisions compared


def control_readings(ctx: dict) -> dict:
    """The check's numbers for the control: the reference with its
    actor's matmul operands in float8, run in the program's place on the
    same seed's first episode."""
    cfg, mix, ref = ctx["config"], ctx["traffic"], ctx["reference"]
    n_fleets = int(mix["fleets"])
    k_agent, k_eps = ref.keys(ctx["seed"])
    p0 = ref.make_weights(cfg, k_agent)
    ep = ref.control(cfg, n_fleets).run(ref.episode_key(k_eps, 0), p0,
                                        int(mix["episode_slots"]))
    return readings(ref, cfg, n_fleets, ctx["seed"], dict(ep, p0=host(p0)))
