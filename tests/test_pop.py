"""Population subsystem: hypers-as-data exactness, PBT surgery
determinism, curriculum sampling/EMA, and the bit-exact mid-PBT
checkpoint resume the training loop's key schedule guarantees."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.policy import agent_def
from repro.mec.env import MECEnv
from repro.mec.scenarios import make_scenario, scenario_space
from repro.pop import (Curriculum, MemberHypers, PBTConfig,
                       PopulationDriver, PopulationTrainer, default_hypers,
                       exit_mask_from_tau, init_population, pbt_update,
                       sample_hypers)
from repro.rollout.driver import RolloutDriver
from repro.train import restore_population, save_population


def tiny_adef(**kw):
    base = dict(buffer_size=16, batch_size=4, train_every=4)
    base.update(kw)
    cfg = make_scenario("fig5_baseline", n_devices=3)
    return agent_def("grle", MECEnv(cfg), **base)


def tiny_space():
    return scenario_space("fig5_baseline", "fig8_csi", n_devices=3)


def tiny_trainer(adef, **kw):
    space = tiny_space()
    base = dict(n_members=4, n_slots=6, mesh=None, pbt_every=1)
    base.update(kw)
    return PopulationTrainer(
        adef, Curriculum(space.lo, space.hi, n_regions=4), **base)


def leaves_equal(a, b) -> bool:
    def eq(x, y):
        x, y = np.asarray(x), np.asarray(y)
        # NaN == NaN here: un-trained stats leaves init to NaN by design
        return np.array_equal(x, y, equal_nan=x.dtype.kind == "f")
    return all(eq(x, y) for x, y in zip(jax.tree_util.tree_leaves(a),
                                        jax.tree_util.tree_leaves(b)))


# --------------------------------------------------------------- population
class TestPopulation:
    def test_init_stacks_member_axis(self):
        adef = tiny_adef()
        pop = init_population(adef, jax.random.PRNGKey(0), 5)
        for leaf in jax.tree_util.tree_leaves(pop.agents):
            assert leaf.shape[0] == 5
        assert int(pop.generation) == 0
        assert pop.hypers.lr.shape == (5,)

    def test_growing_population_keeps_existing_members(self):
        """fold_in per member: member i is independent of P."""
        adef = tiny_adef()
        small = init_population(adef, jax.random.PRNGKey(1), 3)
        large = init_population(adef, jax.random.PRNGKey(1), 6)
        head = jax.tree_util.tree_map(lambda x: x[:3], large.agents)
        assert leaves_equal(small.agents, head)

    def test_sampled_hypers_inside_search_box(self):
        from repro.pop.population import GAIN_RANGE, LR_RANGE, TAU_RANGE
        hyp = sample_hypers(jax.random.PRNGKey(2), 64)
        assert float(hyp.lr.min()) >= LR_RANGE[0]
        assert float(hyp.lr.max()) <= LR_RANGE[1]
        assert float(hyp.explore_gain.min()) >= GAIN_RANGE[0]
        assert float(hyp.explore_gain.max()) <= GAIN_RANGE[1]
        assert float(hyp.exit_tau.min()) >= TAU_RANGE[0]
        assert float(hyp.exit_tau.max()) <= TAU_RANGE[1]

    def test_exit_mask_tau_zero_is_defs_own(self):
        adef = tiny_adef()
        np.testing.assert_array_equal(
            np.asarray(exit_mask_from_tau(adef, 0.0)),
            np.asarray(adef.exit_mask()))

    def test_exit_mask_high_tau_keeps_only_final_exit(self):
        adef = tiny_adef()
        mask = np.asarray(exit_mask_from_tau(adef, 1.1))  # above any acc
        env = adef.env
        per_server = mask.reshape(env.N, env.L)
        base = np.asarray(adef.exit_mask()).reshape(env.N, env.L)
        np.testing.assert_array_equal(per_server[:, :-1], 0.0)
        # the final exit stays exactly as the def's static mask allows
        np.testing.assert_array_equal(per_server[:, -1], base[:, -1])


# ---------------------------------------------------------------------- pbt
class TestPBT:
    def _pop(self, n=4, seed=0):
        adef = tiny_adef()
        key = jax.random.PRNGKey(seed)
        return init_population(adef, key, n,
                               sample_hypers(jax.random.fold_in(key, 1), n))

    def test_same_key_same_surgery(self):
        """The determinism pin: the whole exploit/explore step is a pure
        function of (pop, scores, key)."""
        pop = self._pop()
        scores = jnp.asarray([0.3, 0.9, 0.1, 0.5])
        key = jax.random.PRNGKey(7)
        a, sa = pbt_update(pop, scores, key)
        b, sb = pbt_update(pop, scores, key)
        assert leaves_equal(a, b)
        assert leaves_equal(sa, sb)
        c, _ = pbt_update(pop, scores, jax.random.PRNGKey(8))
        assert not leaves_equal(a.hypers, c.hypers)

    def test_best_overwrites_worst(self):
        pop = self._pop()
        scores = jnp.asarray([0.4, 0.9, 0.1, 0.5])   # worst=2, best=1
        new, stats = pbt_update(pop, scores, jax.random.PRNGKey(0))
        src = np.asarray(stats.src)
        np.testing.assert_array_equal(src, [0, 1, 1, 3])
        np.testing.assert_array_equal(np.asarray(stats.copied), [0, 0, 1, 0])
        np.testing.assert_array_equal(np.asarray(stats.ranks), [2, 0, 3, 1])
        # the loser's agent is a bitwise copy of the winner's
        got = jax.tree_util.tree_map(lambda x: x[2], new.agents)
        want = jax.tree_util.tree_map(lambda x: x[1], pop.agents)
        assert leaves_equal(got, want)

    def test_survivors_keep_state_and_hypers(self):
        pop = self._pop()
        scores = jnp.asarray([0.4, 0.9, 0.1, 0.5])
        new, stats = pbt_update(pop, scores, jax.random.PRNGKey(0))
        for i in np.flatnonzero(np.asarray(stats.copied) < 0.5):
            assert leaves_equal(
                jax.tree_util.tree_map(lambda x: x[i], new.agents),
                jax.tree_util.tree_map(lambda x: x[i], pop.agents))
            assert leaves_equal(
                jax.tree_util.tree_map(lambda x: x[i], new.hypers),
                jax.tree_util.tree_map(lambda x: x[i], pop.hypers))

    def test_perturbed_hypers_stay_in_box(self):
        cfg = PBTConfig(frac=0.5)
        pop = self._pop(n=8, seed=3)
        scores = jnp.arange(8, dtype=jnp.float32)
        new, _ = pbt_update(pop, scores, jax.random.PRNGKey(5), cfg)
        hyp = new.hypers
        assert float(hyp.lr.min()) >= cfg.lr_range[0]
        assert float(hyp.lr.max()) <= cfg.lr_range[1]
        assert float(hyp.explore_gain.min()) >= cfg.gain_range[0]
        assert float(hyp.exit_tau.max()) <= cfg.tau_range[1]

    def test_generation_advances(self):
        pop = self._pop()
        new, _ = pbt_update(pop, jnp.zeros(4), jax.random.PRNGKey(0))
        assert int(new.generation) == int(pop.generation) + 1


# --------------------------------------------------------------- curriculum
class TestCurriculum:
    def _cur(self, **kw):
        space = tiny_space()
        base = dict(n_regions=4)
        base.update(kw)
        return Curriculum(space.lo, space.hi, **base)

    def test_resample_deterministic_in_key(self):
        cur = self._cur()
        st = cur.init_state()
        key = jax.random.PRNGKey(11)
        ra, sa = cur.resample(st, key, 6)
        rb, sb = cur.resample(st, key, 6)
        np.testing.assert_array_equal(np.asarray(ra), np.asarray(rb))
        assert leaves_equal(sa, sb)
        assert np.asarray(ra).min() >= 0
        assert np.asarray(ra).max() < cur.n_regions

    def test_dr_arm_ignores_scores(self):
        cur = self._cur(uniform=True)
        key = jax.random.PRNGKey(4)
        easy = cur.init_state()._replace(
            score=jnp.asarray([9.0, 0.0, 0.0, 9.0]),
            visits=jnp.ones(4))
        ra, _ = cur.resample(cur.init_state(), key, 16)
        rb, _ = cur.resample(easy, key, 16)
        np.testing.assert_array_equal(np.asarray(ra), np.asarray(rb))

    def test_hard_regions_oversampled(self):
        """Low-score (hard) regions dominate the softmax draws."""
        cur = self._cur(temperature=0.3)
        st = cur.init_state()._replace(
            score=jnp.asarray([0.1, 10.0, 10.0, 10.0]),
            visits=jnp.ones(4))
        region, _ = cur.resample(st, jax.random.PRNGKey(0), 64)
        assert np.asarray(region).max() == 0   # odds ~ e^-33 elsewhere

    def test_update_first_visit_seeds_ema(self):
        cur = self._cur(n_regions=3, ema=0.7)
        st = cur.init_state()
        region = jnp.asarray([0, 0, 1], jnp.int32)
        scores = jnp.asarray([1.0, 2.0, 3.0])
        st = cur.update(st, region, scores)
        np.testing.assert_allclose(np.asarray(st.score), [1.5, 3.0, 0.0])
        np.testing.assert_allclose(np.asarray(st.visits), [2.0, 1.0, 0.0])
        # second visit blends: 0.7 * old + 0.3 * batch mean
        st = cur.update(st, jnp.asarray([0], jnp.int32), jnp.asarray([3.0]))
        np.testing.assert_allclose(np.asarray(st.score)[0],
                                   0.7 * 1.5 + 0.3 * 3.0, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(st.score)[1:], [3.0, 0.0])


# -------------------------------------------------- driver + hypers-as-data
class TestPopulationDriver:
    def test_population_of_one_matches_plain_driver(self):
        """Default hypers are exact no-ops: a P=1 generation equals the
        plain scan-fused RolloutDriver episode (lr scale 1.0, gain 0,
        tau 0 are all bit-level identities in the slot body)."""
        adef = tiny_adef()
        key = jax.random.PRNGKey(3)
        pop = init_population(adef, key, 1)        # default hypers
        sp = tiny_space().sample(jax.random.fold_in(key, 9))
        sps = jax.tree_util.tree_map(lambda x: x[None], sp)
        pdrv = PopulationDriver(adef, n_fleets=2, n_slots=8, mesh=None)
        pop2, mets = pdrv.run_generation(pop, key, sps)

        drv = RolloutDriver(adef, n_fleets=2, train=True)
        agent0 = jax.tree_util.tree_map(lambda x: x[0], pop.agents)
        carry, _ = drv.run(jax.random.fold_in(key, 0), 8, mode="scan",
                           agent_state=agent0, sp=sp)
        got = jax.tree_util.tree_map(lambda x: np.asarray(x[0]),
                                     pop2.agents.params)
        for g, w in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(carry.agent_state.params)):
            np.testing.assert_allclose(g, np.asarray(w), rtol=1e-5,
                                       atol=1e-6)

    def test_run_generation_scores_per_member(self):
        adef = tiny_adef()
        key = jax.random.PRNGKey(0)
        n = 3
        pop = init_population(adef, key, n,
                              sample_hypers(jax.random.fold_in(key, 1), n))
        sps = tiny_space().sample_batch(jax.random.fold_in(key, 2), n)
        pdrv = PopulationDriver(adef, n_fleets=1, n_slots=6, mesh=None)
        pop2, mets = pdrv.run_generation(pop, key, sps)
        assert mets["avg_reward"].shape == (n,)
        assert int(pop2.generation) == int(pop.generation)
        assert not leaves_equal(pop.agents, pop2.agents)  # it trained

    def test_evaluate_deterministic_and_training_off(self):
        adef = tiny_adef()
        key = jax.random.PRNGKey(1)
        pop = init_population(adef, key, 2)
        sp = tiny_space().sample(jax.random.fold_in(key, 5))
        pdrv = PopulationDriver(adef, n_fleets=1, n_slots=6, mesh=None)
        a = pdrv.evaluate(pop, key, sp)
        b = pdrv.evaluate(pop, key, sp)
        np.testing.assert_array_equal(np.asarray(a["avg_reward"]),
                                      np.asarray(b["avg_reward"]))

    def test_member_axis_sharded_matches_one_device_subprocess(self):
        """4 fake CPU devices: the member axis mapped over the mesh gives
        the same trained params and scores as the unsharded program, and
        each device holds one member."""
        code = (
            "import jax, numpy as np\n"
            "from repro.core.policy import agent_def\n"
            "from repro.mec.env import MECEnv\n"
            "from repro.mec.scenarios import make_scenario, scenario_space\n"
            "from repro.pop import PopulationDriver, init_population, "
            "sample_hypers\n"
            "from repro.sharding.fleet import fleet_mesh\n"
            "adef = agent_def('grle', MECEnv(make_scenario('fig5_baseline', "
            "n_devices=3)), buffer_size=16, batch_size=4, train_every=4)\n"
            "space = scenario_space('fig5_baseline', 'fig8_csi', "
            "n_devices=3)\n"
            "key = jax.random.PRNGKey(0)\n"
            "pop = init_population(adef, key, 4, "
            "sample_hypers(jax.random.fold_in(key, 1), 4))\n"
            "sps = space.sample_batch(jax.random.fold_in(key, 2), 4)\n"
            "mesh = fleet_mesh()\n"
            "assert mesh is not None and mesh.devices.size == 4\n"
            "out = {}\n"
            "for name, m in (('sharded', mesh), ('one', None)):\n"
            "    pdrv = PopulationDriver(adef, n_fleets=2, n_slots=8, "
            "mesh=m)\n"
            "    pop2, mets = pdrv.run_generation(pop, key, sps)\n"
            "    ev = pdrv.evaluate(pop2, key, space.sample(key))\n"
            "    out[name] = (pop2.agents.params, mets, ev)\n"
            "leaf = jax.tree_util.tree_leaves(out['sharded'][0])[0]\n"
            "assert len({s.device for s in leaf.addressable_shards}) == 4\n"
            "for a, b in zip(jax.tree_util.tree_leaves(out['sharded']),\n"
            "                jax.tree_util.tree_leaves(out['one'])):\n"
            "    np.testing.assert_allclose(np.asarray(a), np.asarray(b), "
            "rtol=1e-4, atol=1e-6)\n"
            "print('POP-SHARDED-OK')\n"
        )
        env = dict(os.environ,
                   XLA_FLAGS="--xla_force_host_platform_device_count=4",
                   JAX_PLATFORMS="cpu",
                   PYTHONPATH="src" + os.pathsep
                   + os.environ.get("PYTHONPATH", ""))
        p = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=600)
        assert p.returncode == 0, p.stderr[-2000:]
        assert "POP-SHARDED-OK" in p.stdout


# ------------------------------------------------------------ trainer/resume
class TestTrainerResume:
    def test_mid_pbt_checkpoint_resume_bit_exact(self, tmp_path):
        """THE resume pin: 2 generations + checkpoint + 2 more in a fresh
        trainer == 4 uninterrupted generations, every leaf bit-equal."""
        adef = tiny_adef()
        straight = tiny_trainer(adef)
        ts_straight, _ = straight.train(straight.init_state(), 4)

        first = tiny_trainer(adef)
        ts, _ = first.train(first.init_state(), 2)
        path = str(tmp_path / "pop.ckpt")
        save_population(path, ts)

        resumed_tr = tiny_trainer(adef)           # no shared state
        ts_resumed = restore_population(path, like=resumed_tr.init_state())
        assert int(ts_resumed.pop.generation) == 2
        ts_resumed, _ = resumed_tr.train(ts_resumed, 2)

        assert leaves_equal(ts_straight, ts_resumed)

    def test_reports_and_telemetry(self):
        adef = tiny_adef()
        tr = tiny_trainer(adef, telemetry=True)
        ts, reports = tr.train(tr.init_state(), 2)
        assert [r["generation"] for r in reports] == [0, 1]
        assert reports[0]["arm"] == "curriculum"
        assert set(reports[0]["metrics"]) >= {
            "mean_reward", "best_reward", "worst_reward", "exploits"}
        from repro.obs.telemetry import telemetry_host
        host = telemetry_host(tr.telemetry)
        assert host["counters"]["generations"] == 2.0
        assert host["counters"]["pbt_rounds"] == 2.0

    def test_history_records_per_generation(self, tmp_path):
        from repro.obs.history import HistoryStore
        store = HistoryStore(str(tmp_path / "hist"))
        adef = tiny_adef()
        tr = tiny_trainer(adef, history=store, history_name="pop_test")
        tr.train(tr.init_state(), 2)
        recs = [r for r in store.records() if r["kind"] == "pop"]
        assert len(recs) == 2
        assert recs[0]["name"] == "pop_test"
        assert "mean_reward" in recs[0]["metrics"]

    def test_population_mesh_divisibility_enforced(self):
        adef = tiny_adef()
        pdrv = PopulationDriver(adef, n_slots=4, mesh=None)
        # mesh=None never raises; fake a mesh via the error path directly
        import repro.sharding.fleet as fleet
        mesh = fleet.fleet_mesh()
        if mesh is None:
            pytest.skip("single-device host: no mesh to violate")
        pdrv = PopulationDriver(adef, n_slots=4, mesh=mesh)
        n = mesh.devices.size + 1
        pop = init_population(adef, jax.random.PRNGKey(0), n)
        sps = tiny_space().sample_batch(jax.random.PRNGKey(1), n)
        with pytest.raises(ValueError, match="not divisible"):
            pdrv.run_generation(pop, jax.random.PRNGKey(2), sps)
