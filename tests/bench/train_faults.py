"""Faults of the training job's timed path, and the number of the check
that has to catch each.

``plant(mp)`` puts every fault into the program at once with a
``pytest.MonkeyPatch``, each behind a switch that the compiled program
reads from the host at run time: the faulted program is traced and
compiled once, and ``switch(name)`` turns one fault on (``None``: none)
for the runs that follow. The CPU tests plant them under tiny runs;
``read_faults.py`` reads them on the chip at the cell's own size.
"""
import jax
import jax.numpy as jnp
import numpy as np

_ON = {"fault": None}


def switch(name) -> None:
    """Turn fault ``name`` on, and every other off, in the planted program."""
    if name is not None and name not in FAULTS:
        raise KeyError(name)
    _ON["fault"] = name


def _on(name: str):
    """A traced bool: whether ``name`` is on when the program runs."""
    return jax.pure_callback(lambda: np.bool_(_ON["fault"] == name),
                             jax.ShapeDtypeStruct((), jnp.bool_))


def _pick(cond, bad, good):
    return jax.tree_util.tree_map(lambda b, g: jnp.where(cond, b, g),
                                  bad, good)


def state_unchanged(policy, env):
    """The train step hands back the state it was given."""
    orig = policy.AgentDef.train_step

    def train_step(self, state, lr=None):
        new, loss = orig(self, state, lr)
        return _pick(_on("state_unchanged"), state, new), loss

    return policy.AgentDef, "train_step", train_step


def half_batch(policy, env):
    """The loss takes the mean over the first half of the minibatch."""
    orig = policy.AgentDef.loss

    def loss(self, params, graphs, decisions, exit_mask):
        n = decisions.shape[0] // 2
        half = jax.tree_util.tree_map(lambda x: x[:n], graphs)
        return jnp.where(_on("half_batch"),
                         orig(self, params, half, decisions[:n], exit_mask),
                         orig(self, params, graphs, decisions, exit_mask))

    return policy.AgentDef, "loss", loss


def decision_altered(policy, env):
    """Each fleet's first device is moved to the next option where the
    decision is made."""
    orig = policy.AgentDef.decide

    def decide(self, state, mec_state, tasks, key, sp=None,
               explore_gain=None):
        d, q, g = orig(self, state, mec_state, tasks, key, sp, explore_gain)
        bad = d.at[0].set((d[0] + 1) % (self.env.N * self.env.L))
        return jnp.where(_on("decision_altered"), bad, d), q, g

    return policy.AgentDef, "decide", decide


def skipped_step(policy, env):
    """The episode's first due train step does nothing and logs no loss."""
    orig = policy.AgentDef.train_step

    def train_step(self, state, lr=None):
        new, loss = orig(self, state, lr)
        skip = _on("skipped_step") & (state.opt_state["step"] == 0)
        return _pick(skip, state, new), jnp.where(skip, jnp.nan, loss)

    return policy.AgentDef, "train_step", train_step


def dropped_candidate(policy, env):
    """The critic leaves the last candidate, an exploration draw, unscored."""
    orig = env.MECEnv.evaluate

    def evaluate(self, state, tasks, decisions, sp=None):
        q = orig(self, state, tasks, decisions, sp)
        return jnp.where(_on("dropped_candidate"),
                         q.at[-1].set(-jnp.inf), q)

    return env.MECEnv, "evaluate", evaluate


def wrong_reward(policy, env):
    """The reward leaves out the exit's accuracy (Eq 9's phi)."""
    orig = env.MECEnv._simulate

    def simulate(self, state, tasks, decision, sp, *, realized):
        res, rest = orig(self, state, tasks, decision, sp,
                         realized=realized)
        psi = 1.0 - jax.nn.sigmoid(5.0 * res.t_total / tasks.deadline_s)
        psi = jnp.where(jnp.isinf(res.t_total), 0.0, psi)
        bad = jnp.sum(jnp.where(tasks.active > 0.5, psi, 0.0))
        return res._replace(reward=jnp.where(_on("wrong_reward"), bad,
                                             res.reward)), rest

    return env.MECEnv, "_simulate", simulate


FAULTS = {
    "state_unchanged": (state_unchanged, "change_gap"),
    "half_batch": (half_batch, "loss_gap"),
    "decision_altered": (decision_altered, "decision_gap"),
    "skipped_step": (skipped_step, "loss_gap"),
    "dropped_candidate": (dropped_candidate, "decision_gap"),
    "wrong_reward": (wrong_reward, "reward_gap"),
}


def plant(mp) -> None:
    """Every fault into the program, all switched off. Faults of one
    method wrap each other, in the order of ``FAULTS``."""
    from repro.core import policy
    from repro.mec import env
    switch(None)
    for make, _ in FAULTS.values():
        owner, name, fn = make(policy, env)
        mp.setattr(owner, name, fn)
