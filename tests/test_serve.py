"""Serving layer: pure scheduler-core invariants, deterministic replay,
sync-vs-async decision equivalence, hot-swap under load.

Everything here runs on virtual time (``serve.clock.VirtualClock``) — no
sleeps, no wall-clock reads — so the whole file is a pure function of
its seeds: the property-style tests drive the scheduler core with a
fixed-seed ``numpy`` RNG, and the engine tests replay fixed loadgen
traces byte-identically.
"""
import json

import jax
import numpy as np
import pytest

from repro.serve import (AgentPool, ContinuousServingEngine,
                         EdgeServingEngine, Replica, VirtualClock, WallClock,
                         ServeRequest, batch_init, batch_occupancy,
                         batch_release, make_trace, queue_depth,
                         queue_expire, queue_init, queue_pop, queue_push,
                         sched_evict, sched_tick)

# small agent so engine tests stay cheap
AGENT_KW = dict(buffer_size=32, batch_size=8, train_every=5, n_candidates=8)


def _arch():
    from repro.configs import get_arch
    return get_arch("qwen1_5_0_5b", reduced=True)


def _replicas():
    return [Replica("a", 1.0), Replica("b", 0.7)]


def _engine(method="grle", batch_slots=4, seed=0, **kw):
    kw.setdefault("workload", "mmpp")
    kw.setdefault("scenario", "dyn_bursty")
    kw.setdefault("agent_kw", AGENT_KW)
    return ContinuousServingEngine(_arch(), _replicas(), scheduler=method,
                                   batch_slots=batch_slots, seed=seed, **kw)


def _req(rid, arrival=0.0, deadline=10.0, priority=0):
    return ServeRequest(rid=rid, arrival_s=arrival, deadline_s=deadline,
                        priority=priority)


# ------------------------------------------------------------------- clocks
class TestClocks:
    def test_virtual_clock_advances_only_on_demand(self):
        c = VirtualClock()
        assert c.now() == 0.0
        assert c.advance(1.5) == 1.5
        assert c.now() == 1.5
        assert c.now() == 1.5          # reading does not advance

    def test_virtual_clock_rejects_negative(self):
        with pytest.raises(ValueError):
            VirtualClock().advance(-1e-9)

    def test_wall_clock_monotone_and_advance_noop(self):
        c = WallClock()
        a = c.now()
        b = c.advance(100.0)           # must NOT jump forward by 100 s
        assert b < 1.0
        assert a <= b <= c.now()


# -------------------------------------------------------------------- queue
class TestQueue:
    def test_push_stamps_monotone_seq(self):
        q = queue_push(queue_init(), [_req(i) for i in range(3)])
        q = queue_push(q, [_req(3)])
        assert [e.seq for e in q.pending] == [0, 1, 2, 3]
        assert q.next_seq == 4
        assert queue_depth(q) == 4

    def test_fifo_within_priority(self):
        reqs = [_req(0, priority=1), _req(1, priority=0),
                _req(2, priority=1), _req(3, priority=0)]
        q = queue_push(queue_init(), reqs)
        q, admitted = queue_pop(q, 3, now=0.0)
        # priority 0 first (in submission order), then the oldest prio 1
        assert [e.req.rid for e in admitted] == [1, 3, 0]
        assert [e.req.rid for e in q.pending] == [2]

    def test_expire_drops_past_deadline(self):
        reqs = [_req(0, deadline=1.0), _req(1, deadline=3.0),
                _req(2, deadline=2.0)]
        q = queue_push(queue_init(), reqs)
        q, expired = queue_expire(q, now=2.0)
        # deadline <= now expires: rids 0 and 2; rid 1 survives
        assert [e.req.rid for e in expired] == [0, 2]
        assert [e.req.rid for e in q.pending] == [1]

    def test_pop_never_admits_dead_requests(self):
        q = queue_push(queue_init(), [_req(0, deadline=1.0),
                                      _req(1, deadline=9.0)])
        q, admitted = queue_pop(q, 2, now=5.0)   # no expire first: belt
        assert [e.req.rid for e in admitted] == [1]
        assert [e.req.rid for e in q.pending] == [0]

    def test_requeue_restores_original_order(self):
        q = queue_push(queue_init(), [_req(i) for i in range(4)])
        q, first = queue_pop(q, 2, now=0.0)      # rids 0, 1 leave
        q = queue_push(q, [_req(4)])             # newer arrival
        from repro.serve import queue_requeue
        q = queue_requeue(q, first)              # 0, 1 come back
        q, admitted = queue_pop(q, 5, now=0.0)
        assert [e.req.rid for e in admitted] == [0, 1, 2, 3, 4]


# ----------------------------------------------------- scheduler-core props
class TestSchedulerInvariants:
    """Property-style: a fixed-seed RNG drives random push/tick/evict/
    release schedules through the pure core; the invariants must hold at
    every intermediate state."""

    N_OPS = 400

    def _random_walk(self, seed, capacity=6):
        rng = np.random.default_rng(seed)
        clock = VirtualClock()
        q, batch = queue_init(), batch_init(capacity)
        submitted, expired_ids, served_ids = [], [], []
        running_rid = 0
        for _ in range(self.N_OPS):
            op = rng.integers(0, 4)
            now = clock.now()
            if op == 0:                                   # push 1-3 requests
                k = int(rng.integers(1, 4))
                reqs = [_req(running_rid + i, arrival=now,
                             deadline=now + float(rng.uniform(0.05, 2.0)),
                             priority=int(rng.integers(0, 3)))
                        for i in range(k)]
                running_rid += k
                submitted += [r.rid for r in reqs]
                q = queue_push(q, reqs)
            elif op == 1:                                 # scheduler tick
                q, batch, ev = sched_tick(q, batch, now)
                expired_ids += [e.req.rid for e in ev.expired]
                for _, e in ev.admitted:
                    # invariant: nothing dead is ever admitted
                    assert e.req.deadline_s > now
            elif op == 2:                                 # evict random slots
                ids = [i for i in range(capacity) if rng.random() < 0.3]
                q, batch, _ = sched_evict(q, batch, ids)
            else:                                         # decode-step release
                # fill holds for fresh admissions (decision happened)
                slots = list(batch.slots)
                for i, r in enumerate(slots):
                    if r is not None and r.hold == 0:
                        slots[i] = r._replace(hold=int(rng.integers(1, 4)))
                batch = batch._replace(slots=tuple(slots))
                batch, released = batch_release(batch)
                served_ids += [r.entry.req.rid for _, r in released]
            # global invariants, every step
            assert 0 <= batch_occupancy(batch) <= capacity
            in_batch = [r.entry.req.rid for r in batch.slots
                        if r is not None]
            assert len(in_batch) == len(set(in_batch))    # no duplicates
            clock.advance(float(rng.uniform(0.0, 0.2)))
        return submitted, expired_ids, served_ids, q, batch, clock

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_invariants_hold_under_random_schedules(self, seed):
        submitted, expired, served, q, batch, clock = self._random_walk(seed)
        # conservation at every horizon: nothing lost, nothing duplicated
        accounted = set(expired) | set(served)
        assert len(expired) == len(set(expired))
        assert len(served) == len(set(served))
        assert set(expired) & set(served) == set()
        pending = {e.req.rid for e in q.pending}
        in_batch = {r.entry.req.rid for r in batch.slots if r is not None}
        assert accounted | pending | in_batch == set(submitted)

    def test_no_request_outlives_deadline_unmarked(self):
        """Drain a queue with short deadlines: every request whose
        deadline passes before admission shows up in ``expired``."""
        clock = VirtualClock()
        reqs = [_req(i, deadline=0.25 + 0.05 * i) for i in range(10)]
        q, batch = queue_push(queue_init(), reqs), batch_init(1)
        seen_expired, seen_served = set(), set()
        while queue_depth(q) or batch_occupancy(batch):
            now = clock.now()
            q, batch, ev = sched_tick(q, batch, now)
            seen_expired |= {e.req.rid for e in ev.expired}
            for _, e in ev.admitted:
                assert e.req.deadline_s > now
            slots = tuple(r._replace(hold=1) if r and r.hold == 0 else r
                          for r in batch.slots)
            batch, released = batch_release(batch._replace(slots=slots))
            seen_served |= {r.entry.req.rid for _, r in released}
            clock.advance(0.2)
        assert seen_expired | seen_served == set(range(10))
        assert seen_expired                      # deadlines short: some died
        # expiry is exact: a request is expired iff it was still pending
        # when the clock passed its deadline — no false expiries
        assert seen_expired & seen_served == set()

    def test_evict_then_readmit_is_idempotent(self):
        q = queue_push(queue_init(),
                       [_req(i, priority=i % 2) for i in range(6)])
        q, batch, ev = sched_tick(q, batch_init(4), now=0.0)
        before = {slot: e.req.rid for slot, e in ev.admitted}
        q, batch, evicted = sched_evict(q, batch, range(4))
        assert batch_occupancy(batch) == 0
        q, batch, ev2 = sched_tick(q, batch, now=0.0)
        after = {slot: e.req.rid for slot, e in ev2.admitted}
        assert after == before                   # same slots, same requests


# --------------------------------------------------------- engine: replay
class TestEngineReplay:
    def test_fixed_seed_trace_replays_byte_identical(self):
        def one_run():
            eng = _engine(batch_slots=8, seed=3)
            trace = make_trace(n_users=12, n_slots=30,
                               slot_s=float(eng.env.cfg.slot_s),
                               deadline_slack_s=0.4, seed=3)
            return json.dumps(eng.run(trace), sort_keys=True), eng
        blob_a, eng_a = one_run()
        blob_b, eng_b = one_run()
        assert blob_a == blob_b                  # byte-identical replay
        assert eng_a.counts == eng_b.counts

    def test_counter_balance_exact_mid_trace_and_drained(self):
        eng = _engine(batch_slots=4, seed=1, hold="latency")
        slot = float(eng.env.cfg.slot_s)
        # 32 users bursting into 4 slots with ~3 slots of slack: the
        # backlog guarantees both servals and queue-side expiries
        trace = make_trace(n_users=32, n_slots=40, slot_s=slot,
                           deadline_slack_s=3 * slot, seed=1)
        # stop mid-trace: balance must hold with requests still in flight
        eng.run(trace, max_steps=10)
        c = eng.counts
        assert c["admitted"] == c["served"] + c["expired"] + eng.in_flight
        eng.run([])                              # drain the rest
        c = eng.counts
        assert eng.in_flight == 0
        assert c["admitted"] == c["served"] + c["expired"]
        assert c["expired"] > 0                  # slack was tight: some died
        # device telemetry mirrors the host counts exactly
        snap = eng.telemetry_snapshot()
        assert snap["counters"]["admitted"] == c["admitted"]
        assert snap["counters"]["served"] == c["served"]
        assert snap["counters"]["expired"] == c["expired"]
        assert snap["summary"]["requests_in_flight"] == 0
        assert snap["summary"]["queue_depth_p99"] is not None
        json.dumps(snap["summary"], allow_nan=False)   # strict JSON

    def test_latency_hold_policy(self):
        eng = _engine(batch_slots=2, seed=0, hold="latency")
        slot = float(eng.env.cfg.slot_s)
        # hold = ceil(latency / slot_s), at least one step; unreachable
        # links (inf) release immediately as misses
        assert eng._hold_steps(0.0) == 1
        assert eng._hold_steps(slot * 0.5) == 1
        assert eng._hold_steps(slot * 3.5) == 4
        assert eng._hold_steps(float("inf")) == 1
        assert _engine(batch_slots=2, seed=0)._hold_steps(slot * 3.5) == 1
        eng.submit([_req(i, deadline=50.0) for i in range(6)])
        while eng.in_flight:
            assert eng.step()["occupancy"] <= 2
        assert eng.counts["served"] == 6

    def test_unknown_hold_policy_rejected(self):
        with pytest.raises(ValueError, match="hold"):
            _engine(hold="forever")


# --------------------------------------------- engine: decision equivalence
class TestSyncAsyncEquivalence:
    """Continuous batching changes *when* requests run, never *what* the
    scheduler decides: replaying the async engine's per-step admission
    groups through the synchronous ``serve_slot`` path reproduces every
    (replica, exit) assignment and the same final agent params."""

    @pytest.mark.parametrize("method", ["grle", "grl", "drooe", "droo"])
    def test_decisions_match_serve_slot(self, method):
        asy = _engine(method=method, batch_slots=4, seed=0)
        trace = make_trace(n_users=6, n_slots=20,
                           slot_s=float(asy.env.cfg.slot_s),
                           deadline_slack_s=5.0, seed=1)
        reports = asy.run(trace)
        syn = EdgeServingEngine(_arch(), _replicas(), scheduler=method,
                                batch_slots=4, seed=0, workload="mmpp",
                                scenario="dyn_bursty", agent_kw=AGENT_KW,
                                init_model=False)
        for rep in reports:
            reqs = [syn.make_request() for _ in rep["assignments"]]
            assignments, _ = syn.serve_slot(reqs)
            got = [(a["replica"], a["exit"]) for a in rep["assignments"]]
            assert got == assignments, f"step {rep['step']} diverged"
        a = asy.get_agent_state()
        b = syn.get_agent_state()
        for x, y in zip(jax.tree_util.tree_leaves(a.params),
                        jax.tree_util.tree_leaves(b.params)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# -------------------------------------------------- engine: hot-swap + A/B
class TestHotSwapUnderLoad:
    def test_agent_and_scenario_swap_drop_nothing(self):
        eng = _engine(batch_slots=4, seed=2, hold="latency")
        trace = make_trace(n_users=10, n_slots=30,
                           slot_s=float(eng.env.cfg.slot_s),
                           deadline_slack_s=0.3, seed=2)
        fresh = eng.agent_def.init(jax.random.PRNGKey(99))
        sp_calm = eng.env.cfg.scenario_params()
        swaps = []

        def on_step(engine, rep):
            if rep["step"] == 5:
                engine.set_agent_state(fresh)
                swaps.append("agent")
            if rep["step"] == 9:
                engine.set_scenario_params(sp_calm)
                swaps.append("scenario")
            if rep["step"] == 13:
                engine.set_scenario_params(None)
                swaps.append("reset")

        reports = eng.run(trace, on_step=on_step)
        assert swaps == ["agent", "scenario", "reset"]
        # every submitted rid leaves exactly once: served or expired
        outcomes = []
        for rep in reports:
            outcomes += [s["rid"] for s in rep["served"]]
            outcomes += rep["expired"]
        assert len(outcomes) == len(set(outcomes))      # no duplicates
        assert sorted(outcomes) == [r.rid for r in trace]  # no drops
        c = eng.counts
        assert c["admitted"] == len(trace)
        assert c["admitted"] == c["served"] + c["expired"]

    def test_ab_pool_round_robin_attribution(self):
        eng = _engine(batch_slots=4, seed=0)
        pool = AgentPool({
            "champion": eng.agent_def.init(jax.random.PRNGKey(0)),
            "challenger": eng.agent_def.init(jax.random.PRNGKey(1)),
        })
        eng.set_agent_pool(pool)
        trace = make_trace(n_users=8, n_slots=24,
                           slot_s=float(eng.env.cfg.slot_s),
                           deadline_slack_s=1.0, seed=4)
        reports = eng.run(trace)
        steps = len(reports)
        st = pool.stats
        assert st["champion"]["steps"] + st["challenger"]["steps"] == steps
        assert abs(st["champion"]["steps"] - st["challenger"]["steps"]) <= 1
        served = st["champion"]["served"] + st["challenger"]["served"]
        assert served == eng.counts["served"] > 0
        hits = st["champion"]["hits"] + st["challenger"]["hits"]
        assert hits == eng.counts["hits"]
        # both variants actually learned while serving
        for name in ("champion", "challenger"):
            assert int(pool.variants[name].step) > 0


# ----------------------------------------------------------------- loadgen
class TestLoadgen:
    def test_arrival_trace_matches_sequential_sample(self):
        from repro.mec import MECEnv, make_scenario
        from repro.rollout import make_workload
        env = MECEnv(make_scenario("dyn_bursty", n_devices=8))
        gen = make_workload(env)
        key = jax.random.PRNGKey(5)
        st0 = gen.init(jax.random.fold_in(key, 1))
        _, active = gen.arrival_trace(st0, jax.random.fold_in(key, 2), 12)
        st, rows = st0, []
        for k in jax.random.split(jax.random.fold_in(key, 2), 12):
            st, tasks = gen.sample(st, k, None)
            rows.append(np.asarray(tasks.active))
        np.testing.assert_array_equal(np.asarray(active), np.stack(rows))

    def test_trace_deterministic_and_ordered(self):
        kw = dict(n_users=16, n_slots=25, slot_s=0.02,
                  deadline_slack_s=0.5, seed=7, priorities=(0, 1))
        a, b = make_trace(**kw), make_trace(**kw)
        assert a == b
        assert [r.rid for r in a] == list(range(len(a)))
        arrivals = [r.arrival_s for r in a]
        assert arrivals == sorted(arrivals)
        assert {r.priority for r in a} <= {0, 1}
        for r in a:
            assert r.deadline_s == r.arrival_s + 0.5

    def test_trace_rejects_iid_and_truncates(self):
        with pytest.raises(ValueError, match="iid"):
            make_trace(scenario="fig5_baseline")
        few = make_trace(n_users=16, n_slots=25, slot_s=0.02, seed=7,
                         max_requests=5)
        assert len(few) == 5


# ------------------------------------------------------- token accounting
class TestTokenAccounting:
    def test_serve_slot_adds_max_new_per_request(self):
        syn = EdgeServingEngine(_arch(), _replicas(), scheduler="grle",
                                batch_slots=4, seed=0, workload="mmpp",
                                scenario="dyn_bursty", agent_kw=AGENT_KW,
                                init_model=False)
        assert syn.tokens_served == 0
        reqs = [syn.make_request(max_new=m) for m in (8, 16, 4)]
        syn.serve_slot(reqs)
        assert syn.tokens_served == 28
        syn.serve_slot([syn.make_request()])
        assert syn.tokens_served == 36
        snap = syn.telemetry_snapshot()
        assert snap["summary"]["tokens_served"] == 36

    def test_continuous_tokens_match_served_budgets(self):
        eng = _engine(batch_slots=4, seed=0)
        trace = make_trace(n_users=8, n_slots=30,
                           slot_s=float(eng.env.cfg.slot_s),
                           deadline_slack_s=5.0, seed=2)
        eng.run(trace)
        served = eng.counts["served"]
        assert served > 0
        assert eng.tokens_served == sum(r.max_new for r in trace[:served])
        snap = eng.telemetry_snapshot()
        assert snap["summary"]["tokens_served"] == eng.tokens_served


# ------------------------------------------------------------- decode loop
_DECODE_ENGINES: dict = {}


def _decode_engine(arch_id):
    """A decoding engine on a reduced architecture, one per test file."""
    from repro.configs import get_arch
    if arch_id not in _DECODE_ENGINES:
        _DECODE_ENGINES[arch_id] = EdgeServingEngine(
            get_arch(arch_id, reduced=True), _replicas(), batch_slots=3,
            cache_len=32, agent_kw=AGENT_KW)
    return _DECODE_ENGINES[arch_id]


def _prompts(engine, shapes, seed=0):
    """Requests of the given (prompt length, max_new) with seeded tokens."""
    from repro.serve import Request
    rng = np.random.default_rng(seed)
    return [Request(tokens=rng.integers(0, engine.cfg.vocab, n)
                    .astype(np.int32), deadline_s=1.0, max_new=m)
            for n, m in shapes]


def _per_position(engine, requests, exit_layer):
    """Greedy, teacher-forced decode one host-dispatched step per
    position: the algorithm the engine's device loop must reproduce."""
    import jax.numpy as jnp

    from repro.train.steps import make_serve_step
    step = jax.jit(make_serve_step(engine.cfg, exit_layer=exit_layer))
    b = len(requests)
    lens = np.array([len(r.tokens) for r in requests])
    total = int(lens.max()) + max(r.max_new for r in requests)
    mat = np.zeros((b, total), np.int32)
    for i, r in enumerate(requests):
        mat[i, : len(r.tokens)] = r.tokens
    cache = engine.model.init_cache(engine.cfg, b, engine.cache_len)
    cur, gen = mat[:, 0], []
    for pos in range(total):
        logits, cache = step(engine.params, cache, jnp.asarray(cur),
                             jnp.full((b,), pos, jnp.int32))
        nxt = np.asarray(jnp.argmax(logits, -1), np.int32)
        gen.append(nxt)
        if pos + 1 < total:
            cur = np.where(pos + 1 < lens, mat[:, pos + 1], nxt)
    gen = np.stack(gen, axis=1)
    return [[int(t) for t in gen[i, lens[i] - 1: lens[i] - 1 + r.max_new]]
            for i, r in enumerate(requests)]


class TestDecodeLoop:
    GROUPS = {1: [(4, 5)], 3: [(3, 4), (6, 2), (1, 6)]}

    @pytest.mark.parametrize("arch_id,exit_at,size", [
        ("qwen1_5_0_5b", 0, 1), ("qwen1_5_0_5b", 0, 3),
        ("qwen1_5_0_5b", -1, 1), ("qwen1_5_0_5b", -1, 3),
        ("deepseek_v2_236b", 0, 3),        # MLA latent cache
        ("rwkv6_7b", -1, 3),               # recurrent state cache
    ])
    def test_decode_matches_per_position_loop(self, arch_id, exit_at, size):
        engine = _decode_engine(arch_id)
        exit_layer = engine.cfg.exit_layers[exit_at]
        reqs = _prompts(engine, self.GROUPS[size], seed=size)
        want = _per_position(engine, reqs, exit_layer)
        assert engine._decode(reqs, exit_layer) == want
        assert [len(t) for t in want] == [r.max_new for r in reqs]

    def test_one_program_for_every_group_length(self):
        engine = _decode_engine("qwen1_5_0_5b")
        exit_layer = engine.cfg.exit_layers[0]
        loop = engine._loops[exit_layer]
        launches = engine.transfers["decode_launches"]
        sizes = []
        for shapes in ([(2, 1), (3, 1)], [(5, 7), (1, 3)],
                       [(9, 12), (4, 20)]):       # totals 4, 12, 29
            engine._decode(_prompts(engine, shapes), exit_layer)
            sizes.append(loop._cache_size())
        assert sizes[0] == sizes[1] == sizes[2]
        assert engine.transfers["decode_launches"] == launches + 3

    def test_group_longer_than_the_cache_raises(self):
        engine = _decode_engine("qwen1_5_0_5b")
        reqs = _prompts(engine, [(20, engine.cache_len - 20 + 1)])
        with pytest.raises(ValueError, match="cache_len"):
            engine._decode(reqs, engine.cfg.exit_layers[0])
        fits = _prompts(engine, [(20, engine.cache_len - 20)])
        assert len(engine._decode(fits, engine.cfg.exit_layers[0])[0]) \
            == engine.cache_len - 20
