"""``Population``: P agents as one pytree, trained by one program.

``AgentState`` is already a pytree, so a *population* is just the same
pytree with a leading member axis [P] — ``jax.vmap(adef.init)`` builds
it, ``tree_map(lambda x: x[idx], ...)`` reshuffles it (how PBT exploits),
and ``train.checkpoint`` serializes it bit-exactly.

Per-member hyperparameters ride along as ``MemberHypers`` — plain [P]
float32 leaves, the same hyperparams-as-data move that made exit masks
data in PR 4:

* ``lr`` — threaded into ``AgentDef.absorb`` as a traced scalar (Adam's
  update is linear in lr, so rescaling updates is exact);
* ``explore_gain`` — biases the random exploration candidates toward the
  actor's own relaxed scores (0 = the def's uniform draw, bit-exactly);
* ``exit_tau`` — a per-member accuracy floor on early exits, turned into
  the member's exit-mask data at generation start
  (``exit_mask_from_tau``).

Because every knob is data, all P members — different lrs, exploration
temperatures, and exit thresholds — share one compiled program, and PBT
can perturb them without a recompile.

``PopulationDriver`` fuses one generation: a jitted ``_begin`` (re-key +
re-mask + fresh episode carries, vmapped over members) and a jitted
``_episode`` (the Algorithm-1 slot body vmapped over (member x fleet)
inside one ``lax.scan``), sharded over devices on the member axis via
``sharding/fleet.py``. Per-slot traces are *not* materialized — member
scores come from the device-resident ``CellMetrics`` accumulator, so
ranking P members costs O(P) scalars of host transfer per generation.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.policy import AgentDef, AgentState
from repro.rollout.driver import RolloutDriver
from repro.rollout.metrics import metrics_finalize
from repro.sharding.fleet import (fleet_mesh, map_leading_axis,
                                  shard_leading_axis)

# Default search box for sampled member hyperparameters (lr is drawn
# log-uniformly; gain/tau uniformly). PBT perturbations clip back into
# the same box (``pbt.PBTConfig``).
LR_RANGE = (3e-4, 3e-3)
GAIN_RANGE = (0.0, 2.0)
TAU_RANGE = (0.0, 0.6)


class MemberHypers(NamedTuple):
    """Per-member hyperparameters as data — [P] float32 leaves.

    Inside the vmapped slot body each member sees scalars; PBT perturbs
    the [P] arrays directly.
    """
    lr: jax.Array            # per-member learning rate
    explore_gain: jax.Array  # exploration bias toward actor scores (>= 0)
    exit_tau: jax.Array      # accuracy floor for allowed early exits


class Population(NamedTuple):
    """P agents + their hyperparameters + the generation counter.

    One registered pytree: checkpoints through ``train.checkpoint``
    (``save_population``/``restore_population``) and reshuffles by
    member-axis gathers.
    """
    agents: AgentState       # stacked on a leading [P] axis
    hypers: MemberHypers     # [P] leaves
    generation: jax.Array    # scalar int32


def default_hypers(adef: AgentDef, n_members: int) -> MemberHypers:
    """Every member at the def's own settings (gain 0 = uniform
    exploration, tau 0 = the def's unmodified exit mask)."""
    f = lambda v: jnp.full((n_members,), v, jnp.float32)
    return MemberHypers(lr=f(adef.lr), explore_gain=f(0.0), exit_tau=f(0.0))


def sample_hypers(key: jax.Array, n_members: int, *,
                  lr_range=LR_RANGE, gain_range=GAIN_RANGE,
                  tau_range=TAU_RANGE) -> MemberHypers:
    """Independent uniform draws per member (log-uniform for lr)."""
    k_lr, k_gain, k_tau = jax.random.split(key, 3)
    log_lo, log_hi = jnp.log(lr_range[0]), jnp.log(lr_range[1])
    lr = jnp.exp(jax.random.uniform(k_lr, (n_members,), jnp.float32,
                                    log_lo, log_hi))
    gain = jax.random.uniform(k_gain, (n_members,), jnp.float32,
                              gain_range[0], gain_range[1])
    tau = jax.random.uniform(k_tau, (n_members,), jnp.float32,
                             tau_range[0], tau_range[1])
    return MemberHypers(lr=lr, explore_gain=gain, exit_tau=tau)


def exit_mask_from_tau(adef: AgentDef, tau) -> jax.Array:
    """[N*L] exit-mask data for one member's accuracy floor ``tau``.

    Exits whose profile accuracy ``exit_acc[l]`` falls below ``tau`` are
    masked off; the final exit always stays allowed (a member must be
    able to serve every task), and the def's own static mask still
    applies — with ``early_exit=False`` tau changes nothing.
    """
    env = adef.env
    acc = env.params.exit_acc                       # [L]
    allow = (acc >= jnp.asarray(tau, jnp.float32)).astype(jnp.float32)
    allow = allow.at[env.L - 1].set(1.0)
    return adef.exit_mask() * jnp.tile(allow, env.N)


def init_population(adef: AgentDef, key: jax.Array, n_members: int,
                    hypers: Optional[MemberHypers] = None) -> Population:
    """Fresh P-member population via ``vmap(adef.init)``.

    Member i's key is ``fold_in(key, i)``, so growing the population
    never perturbs existing members. ``hypers`` defaults to every member
    at the def's own settings — pass ``sample_hypers`` draws for a PBT
    search population.
    """
    agents = jax.vmap(lambda i: adef.init(jax.random.fold_in(key, i)))(
        jnp.arange(n_members))
    return Population(
        agents=agents,
        hypers=hypers if hypers is not None else
        default_hypers(adef, n_members),
        generation=jnp.zeros((), jnp.int32),
    )


class PopulationDriver:
    """One generation for P members as a fixed set of compiled programs.

    Wraps a ``RolloutDriver`` (B fleets per member, shared scenario per
    member) and vmaps its slot body over the member axis — the same
    batching move the sweep packer applies to cells, here applied to
    population members with per-member hyperparameters threaded in as
    traced data. Three jitted programs per driver, independent of P:

    * ``_begin`` — re-key member streams, refresh exit masks from each
      member's ``exit_tau``, build fresh episode carries;
    * ``_episode`` — ``lax.scan`` over slots of
      ``vmap(member)(vmap(fleet))``, returning final carries plus the
      vmapped ``metrics_finalize`` dict ([P] score arrays, no traces);
    * ``_eval_episode`` — the same body with training off (built lazily,
      only when ``evaluate`` is used).

    With a multi-device mesh the member axis is sharded
    (``P % n_devices == 0`` required — padding phantom members would
    distort PBT ranks).
    """

    def __init__(self, adef: AgentDef, *, n_fleets: int = 1,
                 n_slots: int = 100, mesh="auto",
                 replay_capacity: Optional[int] = None,
                 batch_size: Optional[int] = None,
                 train_every: Optional[int] = None):
        self.drv = RolloutDriver(adef, n_fleets=n_fleets, train=True,
                                 replay_capacity=replay_capacity,
                                 batch_size=batch_size,
                                 train_every=train_every)
        self.adef = self.drv.adef
        self.n_fleets = n_fleets
        self.n_slots = int(n_slots)
        self.mesh = fleet_mesh() if mesh == "auto" else mesh
        self._eval_drv: Optional[RolloutDriver] = None
        self._begin_fn = jax.jit(self._begin)
        self._episode_fn = jax.jit(self._episode)
        self._eval_fn = None

    # The jitted programs a compile guard should track, in call order.
    def tracked_programs(self) -> dict:
        return {"pop_begin": self._begin_fn, "pop_episode": self._episode_fn}

    # ------------------------------------------------------------- programs
    def _begin(self, pop: Population, key: jax.Array, sps):
        """Fresh per-member episode carries: member streams are
        ``fold_in(key, member)``; each member's exit mask is re-derived
        from its current ``exit_tau`` (so PBT perturbing tau takes
        effect at the next generation boundary)."""
        n = pop.hypers.lr.shape[0]

        def one(i, agent, tau, sp):
            mask = exit_mask_from_tau(self.adef, tau)
            agent = agent._replace(exit_mask=mask)
            return self.drv.init_carry(jax.random.fold_in(key, i),
                                       agent_state=agent, sp=sp)

        return jax.vmap(one)(jnp.arange(n), pop.agents,
                             pop.hypers.exit_tau, sps)

    def _scan_body(self, drv: RolloutDriver):
        def member(carry, sp, hypers):
            carry, _ = jax.lax.scan(
                lambda c, _: (drv._slot(c, sp, hypers)[0], None),
                carry, None, length=self.n_slots)
            return carry
        return member

    def _episode(self, carries, sps, hypers):
        """Run every member's episode; returns (final carries, metrics
        dict of [P] float32 arrays from ``metrics_finalize``)."""
        carries = map_leading_axis(jax.vmap(self._scan_body(self.drv)),
                                   self.mesh)(carries, sps, hypers)
        mets = jax.vmap(lambda m: metrics_finalize(
            m, slot_s=float(self.adef.env.cfg.slot_s),
            n_fleets=self.n_fleets))(carries.metrics)
        return carries, mets

    # ------------------------------------------------------------ execution
    def _shard(self, tree):
        if self.mesh is None:
            return tree
        return shard_leading_axis(tree, self.mesh)

    def run_generation(self, pop: Population, key: jax.Array, sps):
        """One training generation for the whole population.

        ``sps`` is a [P]-leading ``ScenarioParams`` pytree (one scenario
        per member, shared by its fleets — the curriculum's draws).
        Returns ``(pop with trained agents, metrics dict of [P]
        arrays)``; ranking stays device-resident
        (``metrics["avg_reward"]``).
        """
        n = pop.hypers.lr.shape[0]
        if self.mesh is not None and n % self.mesh.devices.size != 0:
            raise ValueError(
                f"population size {n} not divisible by "
                f"{self.mesh.devices.size} devices (padding would "
                f"distort PBT ranks)")
        carries = self._begin_fn(pop, key, sps)
        carries = self._shard(carries)
        if self.mesh is not None:
            sps = shard_leading_axis(sps, self.mesh)
            hypers = shard_leading_axis(pop.hypers, self.mesh)
        else:
            hypers = pop.hypers
        carries, mets = self._episode_fn(carries, sps, hypers)
        return pop._replace(agents=carries.agent_state), mets

    def evaluate(self, pop: Population, key: jax.Array, sp, *,
                 n_slots: Optional[int] = None):
        """Score every member on one shared scenario, training off.

        ``sp`` is a single (unbatched) ``ScenarioParams`` — broadcast to
        all members so scores are directly comparable. Same key => same
        scores, and the eval program is separate from the training one
        (train=False changes the compiled body). Returns the
        ``metrics_finalize`` dict of [P] arrays.
        """
        if self._eval_drv is None:
            self._eval_drv = RolloutDriver(
                self.adef, n_fleets=self.n_fleets, train=False)

            def ev(pop_, key_, sps_):
                carries = self._begin(pop_, key_, sps_)
                body = map_leading_axis(
                    jax.vmap(self._scan_body(self._eval_drv)), self.mesh)
                carries = body(carries, sps_, pop_.hypers)
                return jax.vmap(lambda m: metrics_finalize(
                    m, slot_s=float(self.adef.env.cfg.slot_s),
                    n_fleets=self.n_fleets))(carries.metrics)

            self._eval_fn = jax.jit(ev)
        n = pop.hypers.lr.shape[0]
        sps = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (n,) + jnp.shape(x)), sp)
        if n_slots is not None and n_slots != self.n_slots:
            raise ValueError("evaluate shares the driver's n_slots; build "
                             "a second PopulationDriver for other lengths")
        return self._eval_fn(pop, key, sps)
