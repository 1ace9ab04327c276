"""Faults of the training job's timed path, each planted in the program
with a ``pytest.MonkeyPatch``, and the number of the check that has to
catch it. The CPU tests plant them under tiny runs; ``read_faults.py``
reads them on the chip at the cell's own size."""
import jax
import jax.numpy as jnp


def state_unchanged(mp):
    """The train step hands back the state it was given."""
    from repro.core import policy
    orig = policy.AgentDef.train_step

    def train_step(self, state, lr=None):
        return state, orig(self, state, lr)[1]

    mp.setattr(policy.AgentDef, "train_step", train_step)


def half_batch(mp):
    """The loss takes the mean over the first half of the minibatch."""
    from repro.core import policy
    orig = policy.AgentDef.loss

    def loss(self, params, graphs, decisions, exit_mask):
        n = decisions.shape[0] // 2
        half = jax.tree_util.tree_map(lambda x: x[:n], graphs)
        return orig(self, params, half, decisions[:n], exit_mask)

    mp.setattr(policy.AgentDef, "loss", loss)


def decision_altered(mp):
    """Each fleet's first device is moved to the next option where the
    decision is made."""
    from repro.core import policy
    orig = policy.AgentDef.decide

    def decide(self, state, mec_state, tasks, key, sp=None,
               explore_gain=None):
        d, q, g = orig(self, state, mec_state, tasks, key, sp, explore_gain)
        return d.at[0].set((d[0] + 1) % (self.env.N * self.env.L)), q, g

    mp.setattr(policy.AgentDef, "decide", decide)


def skipped_step(mp):
    """The episode's first due train step does nothing and logs no loss."""
    from repro.core import policy
    orig = policy.AgentDef.train_step

    def train_step(self, state, lr=None):
        new, loss = orig(self, state, lr)
        skip = state.opt_state["step"] == 0
        kept = jax.tree_util.tree_map(lambda a, b: jnp.where(skip, a, b),
                                      state, new)
        return kept, jnp.where(skip, jnp.nan, loss)

    mp.setattr(policy.AgentDef, "train_step", train_step)


def dropped_candidate(mp):
    """The critic leaves the last candidate, an exploration draw, unscored."""
    from repro.mec import env
    orig = env.MECEnv.evaluate

    def evaluate(self, state, tasks, decisions, sp=None):
        q = orig(self, state, tasks, decisions[:-1], sp)
        return jnp.concatenate([q, jnp.full((1,), -jnp.inf, q.dtype)])

    mp.setattr(env.MECEnv, "evaluate", evaluate)


def wrong_reward(mp):
    """The reward leaves out the exit's accuracy (Eq 9's phi)."""
    from repro.mec import env
    orig = env.MECEnv._simulate

    def simulate(self, state, tasks, decision, sp, *, realized):
        res, rest = orig(self, state, tasks, decision, sp,
                         realized=realized)
        psi = 1.0 - jax.nn.sigmoid(5.0 * res.t_total / tasks.deadline_s)
        psi = jnp.where(jnp.isinf(res.t_total), 0.0, psi)
        reward = jnp.sum(jnp.where(tasks.active > 0.5, psi, 0.0))
        return res._replace(reward=reward), rest

    mp.setattr(env.MECEnv, "_simulate", simulate)


FAULTS = {
    "state_unchanged": (state_unchanged, "change_gap"),
    "half_batch": (half_batch, "loss_gap"),
    "decision_altered": (decision_altered, "decision_gap"),
    "skipped_step": (skipped_step, "loss_gap"),
    "dropped_candidate": (dropped_candidate, "decision_gap"),
    "wrong_reward": (wrong_reward, "reward_gap"),
}
