"""Operations and bytes of GRLE's actor, its two kernels and the train
step, from the shapes alone.

The actor (Eqs 12-14) on one graph of M devices and O = N L options:
two rounds of ``gcn_agg`` per vertex type, relu(h_self W_self + (A h_nbr
/ deg) W_nbr + b), then one ``edge_score``, w_out . relu(h_dev W_src +
b_src + h_opt W_dst + a w_feat) + b_out for every edge. Multiply-adds
count as 2 operations; degree sums, divisions, bias adds and relus are
left out, so every count is a floor. Bytes are float32 operands read
once and results written once, also a floor.
"""
from __future__ import annotations

import numpy as np

FEATURES = (7, 4)     # device and option vertex features
ITEM = 4              # float32


def dims(cfg: dict):
    """(M, O, device features, option features, h1, h2, edge hidden)."""
    w, a = cfg["mec"], cfg["agent"]
    m, o = w["n_devices"], w["n_servers"] * len(w["exit_accuracy"])
    h1, h2 = a["hidden"]
    return m, o, FEATURES[0], FEATURES[1], h1, h2, a["edge_hidden"]


def gcn_agg(b: int, ms: int, mn: int, fs: int, fn: int, h: int):
    """(operations, bytes) of one ``gcn_agg`` call over ``b`` graphs:
    ``ms`` vertices of ``fs`` features aggregate ``mn`` neighbours of
    ``fn`` features into ``h``."""
    flops = 2 * b * (ms * mn * fn + ms * fs * h + ms * fn * h)
    data = (b * (ms * mn + ms * fs + mn * fn + ms * h)
            + fs * h + fn * h + h)
    return flops, ITEM * data


def edge_score(b: int, m: int, o: int, h: int, e: int):
    """(operations, bytes) of one ``edge_score`` call over ``b`` graphs."""
    flops = 2 * b * (m * h * e + o * h * e + 2 * m * o * e)
    data = b * (m * h + o * h + 2 * m * o) + 2 * h * e + 3 * e + 1
    return flops, ITEM * data


def actor_calls(cfg: dict, b: int) -> list:
    """[(kernel, operations, bytes)] of one actor forward over ``b``
    graphs: four ``gcn_agg`` calls and one ``edge_score``."""
    m, o, fd, fo, h1, h2, e = dims(cfg)
    return [("gcn_agg",) + gcn_agg(b, m, o, fd, fo, h1),
            ("gcn_agg",) + gcn_agg(b, o, m, fo, fd, h1),
            ("gcn_agg",) + gcn_agg(b, m, o, h1, h1, h2),
            ("gcn_agg",) + gcn_agg(b, o, m, h1, h1, h2),
            ("edge_score",) + edge_score(b, m, o, h2, e)]


def actor_flops(cfg: dict) -> int:
    """Operations of the actor's forward on one graph."""
    return sum(f for _, f, _ in actor_calls(cfg, 1))


def due_slots(cfg: dict, n_fleets: int, n_slots: int) -> np.ndarray:
    """[T] True at the slots where a train step is due: every
    ``train_every`` slots once the replay ring holds a minibatch (each
    slot adds one graph per fleet)."""
    a = cfg["agent"]
    s = np.arange(1, n_slots + 1)
    return ((s % a["train_every"] == 0)
            & (np.minimum(s * n_fleets, a["replay"]) >= a["minibatch"]))


def train_steps(cfg: dict, n_fleets: int, n_slots: int) -> int:
    """Train steps in an episode."""
    return int(due_slots(cfg, n_fleets, n_slots).sum())


def episode_flops(cfg: dict, n_fleets: int, n_slots: int) -> int:
    """Model operations of one episode: an actor forward per fleet and
    slot, and for each train step a forward and a backward (counted as
    twice the forward) over the minibatch."""
    steps = train_steps(cfg, n_fleets, n_slots)
    per_graph = actor_flops(cfg)
    return (n_slots * n_fleets * per_graph
            + steps * 3 * cfg["agent"]["minibatch"] * per_graph)


def episode_kernel_calls(cfg: dict, n_fleets: int, n_slots: int) -> list:
    """Every kernel call of one episode: each slot's actor forward over
    all fleets at once, and each train step's forward over the
    minibatch (the backward runs no kernel)."""
    steps = train_steps(cfg, n_fleets, n_slots)
    return (n_slots * actor_calls(cfg, n_fleets)
            + steps * actor_calls(cfg, cfg["agent"]["minibatch"]))
