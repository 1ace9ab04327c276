"""Plain reference of the paper's training job, for the correctness check.

GRLE (arXiv:2210.13464, Algorithm 1) on the MEC network of Section VI-A:
each slot every fleet draws its tasks (Eqs 1-5), the two-layer GCN actor
(Eqs 12-14) scores every (device, option) edge, the quantizer proposes
candidates, the model-based critic (Eq 15) scores each by the slot's
reward under FCFS queueing (Eqs 6-11) and keeps the best; the slot's
(graph, decision) pairs enter a replay ring, and every ``train_every``
slots the actor takes one Adam step on the cross-entropy of a minibatch
(Eq 16).

Written from the paper and the configuration alone: float32, matmuls at
``highest`` precision, dense adjacency products, no kernels, and nothing
imported from the program. It reproduces the configuration's stated key
layout (``assumed.keys``), so with the same seed it draws the same tasks,
exploration candidates, initial weights and minibatches as the program.

``Reference.run(..., follow=decisions)`` steps the world and the replay
with a subject's decisions and reports, at each slot, the decision the
reference itself would take there: the check compares the two without
letting one disagreement steer the rest of the episode. ``control`` is
the same reference with the actor's matmul operands rounded to float8
(e4m3), the precision below the configuration's: float32 at the default
matmul precision, which on a TPU takes bfloat16 operands.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
FEATURES = (7, 4)     # device and option vertex features (``assumed``)


def keys(seed: int):
    """(agent key, episode keys) for a seed of any size."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    k_agent, k_eps = jax.random.split(jnp.asarray(words, jnp.uint32))
    return k_agent, k_eps


def episode_key(k_eps, i: int):
    return jax.random.fold_in(k_eps, i)


class World(NamedTuple):
    """Section VI-A's network, as hashable numbers."""
    m: int
    n: int
    l: int
    slot_s: float
    deadline_s: float
    task_kb: tuple
    rate_mbps: tuple
    times: tuple            # [N][L] seconds
    acc: tuple              # [L]

    @property
    def deadline(self):
        return jnp.float32(self.deadline_s)


def world(cfg: dict) -> World:
    w = cfg["mec"]
    for k in ("csi_error", "inference_jitter", "link_drop"):
        if w[k] != 0:
            raise ValueError(f"the reference models Section VI-A's static "
                             f"network: {k} must be 0")
    if w["capacity"] != 1:
        raise ValueError("the reference models full server capacity")
    times = np.asarray(w["exit_ms"], np.float64) * 1e-3
    return World(m=w["n_devices"], n=w["n_servers"], l=times.shape[1],
                 slot_s=w["slot_s"], deadline_s=w["deadline_s"],
                 task_kb=tuple(w["task_kbytes"]),
                 rate_mbps=tuple(w["rate_mbps"]),
                 times=tuple(map(tuple, times.tolist())),
                 acc=tuple(w["exit_accuracy"]))


# ------------------------------------------------------------------ actor
def _linear(key, fan_in: int, fan_out: int, bias: bool = True) -> dict:
    """Glorot-uniform weights, zero bias."""
    kw, _ = jax.random.split(key)
    lim = math.sqrt(6.0 / (fan_in + fan_out))
    p = {"w": jax.random.uniform(kw, (fan_in, fan_out), F32, -lim, lim)}
    if bias:
        p["b"] = jnp.zeros((fan_out,), F32)
    return p


def make_weights(cfg: dict, key) -> dict:
    """The actor's initial weights from the agent key: two GCN rounds
    per vertex type (self and neighbour halves of a concat-linear), then
    the edge MLP's source, option and edge-feature projections and its
    scalar head."""
    a = cfg["agent"]
    h1, h2 = a["hidden"]
    e = a["edge_hidden"]
    fd, fo = FEATURES
    key = jax.random.fold_in(key, 0xC0FFEE)
    ks = jax.random.split(jax.random.split(key)[0], 8)
    return {
        "dev1": _linear(ks[0], fd + fo, h1),
        "opt1": _linear(ks[1], fo + fd, h1),
        "dev2": _linear(ks[2], 2 * h1, h2),
        "opt2": _linear(ks[3], 2 * h1, h2),
        "edge_src": _linear(ks[4], h2, e),
        "edge_dst": _linear(ks[5], h2, e, bias=False),
        "edge_feat": _linear(ks[6], 1, e, bias=False),
        "edge_out": _linear(ks[7], e, 1),
    }


def actor(p: dict, dev, opt, adj, dt=F32):
    """Edge logits [..., M, O] (Eqs 12-14). ``dt`` is the type the
    matmuls' operands are rounded to; products accumulate in float32 and
    gradients pass the rounding unchanged."""
    def rounded(x):
        if jnp.dtype(dt) == F32:
            return x
        return x + jax.lax.stop_gradient(x.astype(dt).astype(F32) - x)

    def mm(x, y):
        return jnp.matmul(rounded(x), rounded(y))

    def gcn(h_self, h_nbr, a, lin):
        f = h_self.shape[-1]
        agg = mm(a, h_nbr) / (a.sum(-1, keepdims=True) + 1e-6)
        return jax.nn.relu(mm(h_self, lin["w"][:f]) + mm(agg, lin["w"][f:])
                           + lin["b"])

    adj_t = jnp.swapaxes(adj, -1, -2)
    d1, o1 = gcn(dev, opt, adj, p["dev1"]), gcn(opt, dev, adj_t, p["opt1"])
    d2, o2 = gcn(d1, o1, adj, p["dev2"]), gcn(o1, d1, adj_t, p["opt2"])
    src = mm(d2, p["edge_src"]["w"]) + p["edge_src"]["b"]
    dst = mm(o2, p["edge_dst"]["w"])
    hidden = jax.nn.relu(src[..., :, None, :] + dst[..., None, :, :]
                         + adj[..., None] * p["edge_feat"]["w"][0])
    return mm(hidden, p["edge_out"]["w"])[..., 0] + p["edge_out"]["b"][0]


def bce(p, dev, opt, adj, dec, dt=F32):
    """Eq 16: the actor's edge probabilities against the one-hot critic
    decisions, averaged over edges and graphs."""
    logits = actor(p, dev, opt, adj, dt)
    target = jax.nn.one_hot(dec, logits.shape[-1], dtype=F32)
    return jnp.mean(jax.nn.softplus(logits) - logits * target)


# ------------------------------------------------------------------ world
def tasks(w: World, key):
    """One fleet's iid slot draw: task sizes [M] (bits), uplink rates
    [M, N] (bit/s)."""
    ks = jax.random.split(key, 7)
    size = jax.random.uniform(ks[0], (w.m,), minval=w.task_kb[0],
                              maxval=w.task_kb[1]) * 8e3
    rate = jax.random.uniform(ks[1], (w.m, w.n), minval=w.rate_mbps[0],
                              maxval=w.rate_mbps[1]) * 1e6
    return size, rate


def graph(w: World, dev_free, es_free, slot, size, rate):
    """Vertex features and the weighted [M, O] adjacency the actor sees."""
    now = slot.astype(F32) * w.slot_s
    inv = 1.0 / w.deadline
    r = rate * (1.0 / (jnp.float32(w.rate_mbps[1]) * 1e6))
    ones = jnp.ones((w.m,), F32)
    dev = jnp.stack([
        size * (1.0 / (jnp.float32(w.task_kb[1]) * 8e3)), ones,
        r.mean(-1), r.max(-1),
        jnp.log1p(jnp.maximum(dev_free - now, 0.0) * inv), ones,
        jnp.arange(w.m, dtype=F32) / max(w.m - 1, 1)], -1)
    shape = (w.n, w.l)
    opt = jnp.stack([
        jnp.asarray(w.times, F32) * inv,
        jnp.broadcast_to(jnp.asarray(w.acc, F32), shape),
        jnp.broadcast_to(jnp.log1p(jnp.maximum(es_free - now, 0.0)
                                   * inv)[:, None], shape),
        jnp.ones(shape, F32)], -1).reshape(w.n * w.l, 4)
    return dev, opt, jnp.repeat(r, w.l, axis=-1)


def simulate(w: World, dev_free, es_free, slot, size, rate, d):
    """A decision's slot (Eqs 1-11): each device sends when its uplink is
    free, each server serves in order of arrival (ties by device). Returns
    (reward, uplink free times, server free times)."""
    srv, ex = d // w.l, d % w.l
    now = slot.astype(F32) * w.slot_s
    t_com = size / jnp.maximum(rate[jnp.arange(w.m), srv], 1.0)
    arrival = jnp.maximum(dev_free, now) + t_com
    t_cmp = jnp.asarray(w.times, F32)[srv, ex]
    order = jnp.lexsort((arrival, srv))

    def serve(busy, i):
        start = jnp.maximum(arrival[i], busy[srv[i]])
        return busy.at[srv[i]].set(start + t_cmp[i]), start

    busy, started = jax.lax.scan(serve, es_free, order)
    start = jnp.zeros((w.m,), F32).at[order].set(started)
    t_total = t_com + (start - arrival) + t_cmp
    psi = 1.0 - jax.nn.sigmoid(5.0 * t_total * (1.0 / w.deadline))
    return jnp.sum(jnp.asarray(w.acc, F32)[ex] * psi), arrival, busy


def candidates(x, n: int):
    """Order-preserving quantization: each device's best option, then
    that decision with one device moved to another option, in order of
    the score given up."""
    m, o = x.shape
    best = jnp.argmax(x, -1)
    rows = jnp.arange(m)
    margin = (x[rows, best][:, None] - x).at[rows, best].set(jnp.inf)
    flat = margin.reshape(-1)
    order = jnp.argsort(flat, stable=True)[: n - 1]
    dev, opt = order // o, order % o
    opt = jnp.where(flat[order] < 1e8, opt, best[dev])
    cands = jnp.tile(best[None], (n, 1))
    return cands.at[jnp.arange(1, n), dev].set(opt).astype(jnp.int32)


# -------------------------------------------------------------- the job
class Reference(NamedTuple):
    """The job for ``b`` fleets sharing one learner; ``dt`` names the
    actor's matmul type. Hashable, so every instance alike shares one
    compiled slot."""
    w: World
    a: tuple          # the configuration's ``agent`` items
    b: int
    dt: str = "float32"

    @property
    def agent(self) -> dict:
        return dict(self.a)

    def start(self, key, params, opt_state=None) -> dict:
        w, a = self.w, self.agent
        cap = a["replay"]
        k_task, k_dec, k_agent, _ = jax.random.split(key, 4)
        fleets = jnp.arange(self.b)

        def streams(k):
            return jax.vmap(lambda i: jax.random.fold_in(k, i))(fleets)

        zeros = lambda p: jax.tree_util.tree_map(jnp.zeros_like, p)
        mu, nu, t = opt_state or (zeros(params), zeros(params),
                                  jnp.zeros((), jnp.int32))
        o = w.n * w.l
        return dict(
            task=streams(k_task), dec=streams(k_dec),
            key=jax.random.split(k_agent)[1],
            dev_free=jnp.zeros((self.b, w.m), F32),
            es_free=jnp.zeros((self.b, w.n), F32),
            slot=jnp.zeros((), jnp.int32),
            params=params, mu=mu, nu=nu, t=t,
            ring=dict(dev=jnp.zeros((cap, w.m, FEATURES[0]), F32),
                      opt=jnp.zeros((cap, o, FEATURES[1]), F32),
                      adj=jnp.zeros((cap, w.m, o), F32),
                      dec=jnp.zeros((cap, w.m), jnp.int32)),
            ptr=jnp.zeros((), jnp.int32), size=jnp.zeros((), jnp.int32),
            g1=zeros(params))

    def decide(self, params, dev_free, es_free, slot, size, rate, key):
        w, a = self.w, self.agent
        dev, opt, adj = graph(w, dev_free, es_free, slot, size, rate)
        x = jax.nn.sigmoid(actor(params, dev, opt, adj, self.dt))
        rand = jnp.argmax(jax.random.gumbel(
            key, (a["n_random"], w.m, w.n * w.l)), -1).astype(jnp.int32)
        cands = jnp.concatenate([candidates(x, a["candidates"]), rand])
        q = jax.vmap(lambda d: simulate(w, dev_free, es_free, slot, size,
                                        rate, d)[0])(cands)
        return cands[jnp.argmax(q)], (dev, opt, adj)

    def train(self, s: dict):
        a = self.agent
        key, k_samp = jax.random.split(s["key"])
        scores = jax.random.uniform(jax.random.split(k_samp)[0],
                                    (a["replay"],))
        scores = jnp.where(jnp.arange(a["replay"]) < s["size"], scores,
                           jnp.inf)
        take = jnp.argsort(scores)[: a["minibatch"]]
        r = s["ring"]
        loss, g = jax.value_and_grad(bce)(
            s["params"], r["dev"][take], r["opt"][take], r["adj"][take],
            r["dec"][take], self.dt)
        b1, b2, eps = a["adam"]
        t = s["t"] + 1
        tf = t.astype(F32)
        mu = jax.tree_util.tree_map(lambda m, x: b1 * m + (1 - b1) * x,
                                    s["mu"], g)
        nu = jax.tree_util.tree_map(lambda v, x: b2 * v + (1 - b2) * x * x,
                                    s["nu"], g)
        params = jax.tree_util.tree_map(
            lambda p, m, v: p - a["lr"] * (m / (1 - b1 ** tf))
            / (jnp.sqrt(v / (1 - b2 ** tf)) + eps), s["params"], mu, nu)

        def pick(cond, new, old):
            return jax.tree_util.tree_map(lambda x, y: jnp.where(cond, x, y),
                                          new, old)

        s = dict(s, key=key, params=params, mu=mu, nu=nu, t=t,
                 g1=pick(s["t"] == 0, g, s["g1"]))
        return s, loss.astype(F32)

    def slot(self, s: dict, follow, use_follow):
        w, a = self.w, self.agent
        split = jax.vmap(lambda k: tuple(jax.random.split(k)))
        task, task_sub = split(s["task"])
        dec, dec_sub = split(s["dec"])
        size, rate = jax.vmap(lambda k: tasks(w, k))(task_sub)
        own, (dev, opt, adj) = jax.vmap(
            self.decide, in_axes=(None, 0, 0, None, 0, 0, 0))(
            s["params"], s["dev_free"], s["es_free"], s["slot"], size, rate,
            dec_sub)
        d = jnp.where(use_follow, follow, own)
        reward, dev_free, es_free = jax.vmap(
            simulate, in_axes=(None, 0, 0, None, 0, 0, 0))(
            w, s["dev_free"], s["es_free"], s["slot"], size, rate, d)
        cap = a["replay"]
        idx = (s["ptr"] + jnp.arange(self.b)) % cap
        ring = {k: s["ring"][k].at[idx].set(v)
                for k, v in dict(dev=dev, opt=opt, adj=adj, dec=d).items()}
        step = s["slot"] + 1
        s = dict(s, task=task, dec=dec, dev_free=dev_free, es_free=es_free,
                 slot=step, ring=ring, ptr=(s["ptr"] + self.b) % cap,
                 size=jnp.minimum(s["size"] + self.b, cap))
        due = (step % a["train_every"] == 0) & (s["size"] >= a["minibatch"])
        s, loss = jax.lax.cond(due, self.train,
                               lambda s: (s, jnp.full((), jnp.nan, F32)), s)
        return s, (own, reward, loss)

    def run(self, key, params, n_slots: int, follow=None, opt_state=None):
        """One episode from ``params`` (and Adam's ``(mu, nu, t)``, zero
        by default). With ``follow`` [T, B, M] the world and the replay
        take those decisions. Returns the reference's own decisions
        [T, B, M], the rewards of the decisions taken [T, B], the losses
        [T] (NaN where no step was due), the first step's gradient
        ``g1``, and the final ``params`` and Adam's first moment ``mu``."""
        out = []
        with jax.default_matmul_precision("highest"):
            s = self.start(key, params, opt_state)
            zero = jnp.zeros((self.b, self.w.m), jnp.int32)
            for i in range(n_slots):
                f = zero if follow is None else jnp.asarray(follow[i])
                s, o = _slot(self, s, f, follow is not None)
                out.append(o)
        own, reward, loss = (np.stack([np.asarray(o[i]) for o in out])
                             for i in range(3))
        host = lambda t: jax.tree_util.tree_map(np.asarray, t)
        return dict(decisions=own, reward=reward, loss=loss, g1=host(s["g1"]),
                    params=host(s["params"]), mu=host(s["mu"]))


_slot = jax.jit(Reference.slot, static_argnums=0)


def _frozen(x):
    return tuple(map(_frozen, x)) if isinstance(x, list) else x


def reference(cfg: dict, n_fleets: int, dt: str = "float32") -> Reference:
    """The job of ``cfg`` for ``n_fleets`` fleets."""
    return Reference(world(cfg), tuple(sorted(
        (k, _frozen(v)) for k, v in cfg["agent"].items())), n_fleets, dt)


def control(cfg: dict, n_fleets: int) -> Reference:
    """The reference with the actor's matmul operands in float8 (e4m3)."""
    return reference(cfg, n_fleets, "float8_e4m3fn")
