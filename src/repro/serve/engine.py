"""Edge-serving engines: GRLE scheduling multi-exit LM inference.

The integration the paper implies lifted to transformers (DESIGN.md §4):
"edge servers" are model replicas (mesh slices) with heterogeneous speed;
tasks are generation requests with deadlines; the GRLE agent picks
(replica, exit depth) per request batch; decoding uses the per-exit
``serve_step`` variants (the exit choice is a compile-time schedule
truncation).

Two engines share one world model (``_ServingCore``: the MEC simulator
with an analytic per-exit latency table in place of Table I, the
workload generator, the pure-functional scheduler agent, telemetry):

* ``EdgeServingEngine`` — the synchronous slot loop: the caller hands
  ``serve_slot`` up to ``batch_slots`` requests (or lets the arrival
  process draw them) and everything completes within the call.
* ``ContinuousServingEngine`` — the async, continuously-batched path:
  requests enter a deadline-aware queue (``serve.queue``), a **pure**
  scheduler core (``sched_tick``/``sched_evict``/``batch_release`` — a
  function of queue state, batch state, and an explicit clock) admits
  and evicts per decode step, and one batched GRLE actor program prices
  the whole batch at once — no per-exit recompiles on the scheduling
  plane. Driven by a ``serve.clock`` clock: a ``VirtualClock`` makes the
  entire loop deterministic under test; a ``WallClock`` serves live.

Request load can be externally supplied (``serve_slot(requests)`` /
``ContinuousServingEngine.submit``, e.g. from ``serve.loadgen``) or
arrival-driven (``serve_slot()`` with ``workload="poisson"``/``"mmpp"``).
"""
from __future__ import annotations

import collections
import dataclasses
import math
from typing import Iterable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.policy import AgentState, agent_def
from repro.mec.config import MECConfig, ScenarioParams
from repro.mec.env import MECEnv
from repro.mec.scenarios import SCENARIOS
from repro.mec.metrics import RunningMetrics
from repro.mec.profiles import llm_exit_profile
from repro.models.config import ArchConfig
from repro.models.lm import model_for
from repro.obs.profile import pull, span
from repro.obs.telemetry import (hist_quantile, rollout_telemetry,
                                 serve_telemetry, serve_telemetry_update,
                                 telemetry_host, telemetry_summary,
                                 telemetry_update)
from repro.rollout.workloads import make_workload
from repro.serve.clock import VirtualClock
from repro.serve.queue import (QueueEntry, QueueState, ServeRequest,
                               queue_depth, queue_expire, queue_init,
                               queue_pop, queue_push, queue_requeue)
from repro.train.steps import make_serve_step


@dataclasses.dataclass
class Request:
    tokens: np.ndarray          # prompt token ids
    deadline_s: float
    max_new: int = 8


@dataclasses.dataclass
class Replica:
    """One model replica ('edge server'). speed < 1 models a slower chip."""
    name: str
    speed: float = 1.0


# ===================================================================== core
class _ServingCore:
    """World model + scheduler agent shared by both serving engines.

    Owns everything except the serving *loop*: the MEC simulator with
    the LM exit-profile latency table, the arrival-process generator,
    the pure-functional GRLE agent (hot-swappable via
    ``get/set_agent_state``), scenario hot-swap
    (``set_scenario_params``), telemetry and the exact latency ring.
    Both engines consume construction RNG identically, so a sync and an
    async engine built from the same seed share bit-identical agent
    parameters and workload streams — the decision-equivalence pin in
    ``tests/test_serve.py`` relies on this.
    """

    def __init__(self, cfg: ArchConfig, replicas: list[Replica], *,
                 key=None, cache_len: int = 256, scheduler: str = "grle",
                 batch_slots: int = 4, seed: int = 0,
                 workload: Optional[str] = None,
                 arrival_rate: Optional[float] = None,
                 scenario: Optional[str] = None,
                 use_pallas: Optional[bool] = None,
                 latency_ring: int = 512,
                 agent_kw: Optional[dict] = None,
                 init_model: bool = True):
        """``scenario`` names a ``repro.mec.SCENARIOS`` entry whose dynamic
        knobs (capacity range, jitter, CSI error, workload process, ...)
        overlay the engine's MEC world model — exit tables and shape stay
        the engine's own, and explicitly passed ``workload=``/
        ``arrival_rate=`` always win over the scenario's. Numeric knobs
        can also be hot-swapped later via ``set_scenario_params`` without
        recompiling. Defaults without a scenario: ``workload="iid"``,
        ``arrival_rate=0.7``. ``use_pallas`` is the scheduler's kernel
        backend switch (None auto-selects: Pallas on TPU, jnp reference
        elsewhere) — the same batched actor program the rollout and sweep
        layers run. ``latency_ring`` bounds the exact last-K request
        latency window ``telemetry_snapshot`` derives its
        ``latency_p50_s_exact``/``latency_p99_s_exact`` from.
        ``agent_kw`` forwards extra ``AgentDef`` knobs (e.g. a smaller
        ``n_candidates`` for wide serving batches); ``init_model=False``
        skips LM parameter initialization for scheduling-plane-only use
        (the analytic exit table needs only the architecture shape).
        """
        key = key if key is not None else jax.random.PRNGKey(seed)
        self.cfg = cfg
        self.model = model_for(cfg) if init_model else None
        self.params = self.model.init(key, cfg) if init_model else None
        self.replicas = replicas
        self.cache_len = cache_len
        self.batch_slots = batch_slots

        # per-exit latency/quality profile (the Table-I analogue)
        times, quality = llm_exit_profile(
            cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.vocab, cfg.exit_layers,
            kv_len=cache_len)
        times = np.concatenate(
            [times / r.speed for r in replicas], axis=0)       # [N, L]
        self.exit_times = times
        self.exit_quality = quality

        # deadline must cover uplink time (≈ 0.3–6.4 ms at 4–16 KB prompts
        # over 20–100 Mbps) plus a few compute slots — same regime as the
        # paper's 30 ms budget.
        deadline = max(20e-3, float(times.max()) * 6)
        mec_kwargs = dict(
            task_kbytes=(4.0, 16.0), rate_mbps=(20.0, 100.0),
            capacity_range=(0.5, 1.0),
        )
        if scenario is not None:
            # scenario dynamics overlay the defaults; structural fields
            # stay the engine's (its exit tables ARE the Table-I analogue)
            overlay = dict(SCENARIOS[scenario])
            for k in ("n_devices", "n_servers", "exit_times_s",
                      "exit_accuracy", "slot_s", "deadline_s"):
                overlay.pop(k, None)
            mec_kwargs.update(overlay)
        # explicit constructor args beat the scenario's arrival process
        if workload is not None:
            mec_kwargs["workload"] = workload
        if arrival_rate is not None:
            mec_kwargs["arrival_rate"] = arrival_rate
        mec_kwargs.setdefault("workload", "iid")
        mec_kwargs.setdefault("arrival_rate", 0.7)
        mec_cfg = MECConfig(
            n_devices=batch_slots, n_servers=len(replicas),
            exit_times_s=tuple(map(tuple, times.tolist())),
            exit_accuracy=tuple(quality.tolist()),
            slot_s=deadline / 2, deadline_s=deadline,
            **mec_kwargs,
        )
        self.env = MECEnv(mec_cfg)
        # live scenario knobs: None -> the config's own; see
        # set_scenario_params for recompile-free swaps
        self._sp = None
        self.mec_state = self.env.reset()
        # arrival process: with workload != "iid" the generator's ``active``
        # mask decides which batch slots carry a request each slot
        self._workload = make_workload(self.env)
        self._wl_state = self._workload.init(jax.random.fold_in(key, 1))
        self._req_rng = np.random.default_rng(seed)
        # pure-functional scheduler: the def is static structure, the
        # state is one hot-swappable pytree (see get/set_agent_state)
        self.agent_def = (agent_def(scheduler, self.env,
                                    use_pallas=use_pallas,
                                    **(agent_kw or {}))
                          if scheduler else None)
        self.agent_state = (self.agent_def.init(key)
                            if self.agent_def is not None else None)
        self._agent_step = (jax.jit(self.agent_def.step)
                            if self.agent_def is not None else None)
        self.metrics = RunningMetrics(slot_s=mec_cfg.slot_s)
        # device-resident request telemetry ([M]-batched updates, pulled
        # to host only by telemetry_snapshot) + host transfer counters
        self.telemetry = self._make_telemetry()
        # exact last-K request latencies (seconds, finished requests
        # only) next to the bucketed histogram: the histogram's p99 is a
        # bin-edge interpolation, the ring's is the true order statistic
        # over the recent window
        self._latency_ring: collections.deque = collections.deque(
            maxlen=latency_ring)
        # generated-token accounting: each served request contributes its
        # ``max_new`` budget (the synthetic decode payload is exactly that
        # long), so throughput reads as tokens/s next to requests/s
        self.tokens_served = 0
        self.transfers = {"decode_h2d": 0, "decode_d2h": 0,
                          "decode_launches": 0, "telemetry_pulls": 0,
                          "host_pulls": 0}
        self._tel_update = jax.jit(
            lambda tel, dec, res, act, dl, rf, loss: telemetry_update(
                tel, decisions=dec, result=res, active=act, deadline_s=dl,
                replay_frac=rf, loss=loss, n_exits=self.env.L))
        self._key = key

    def _make_telemetry(self):
        return rollout_telemetry(self.env.N, self.env.L)

    def _pull(self, x) -> np.ndarray:
        """One device->host read, spanned and counted in ``transfers``."""
        return pull(x, self.transfers)

    # ---------------------------------------------------------- shared step
    def _price_slot(self, active: np.ndarray):
        """One scheduling step over the current batch occupancy mask.

        Splits the engine key, draws the slot's world from the arrival
        generator, overlays ``active`` (the real request occupancy), and
        runs the batched agent program (or the static fallback). Returns
        (tasks, decision [M] np, result) after stepping the env and
        telemetry. This is THE shared decision body: the sync and async
        engines differ only in who computes ``active``.
        """
        self._key, sk = jax.random.split(self._key)
        self._wl_state, tasks = self._workload.sample(self._wl_state, sk,
                                                      self._sp)
        if active is not None:
            tasks = tasks._replace(active=jnp.asarray(active, jnp.float32))
        if self.agent_def is not None:
            self.agent_state, decision, aux = self._agent_step(
                self.agent_state, self.mec_state, tasks, None, self._sp)
            loss = aux.loss
            replay_frac = (self.agent_state.replay.size.astype(jnp.float32)
                           / float(self.agent_def.buffer_size))
        else:  # static: final exit, round-robin replica
            L = self.env.L
            decision = jnp.asarray(
                [(i % self.env.N) * L + (L - 1)
                 for i in range(self.batch_slots)], jnp.int32)
            loss = jnp.full((), jnp.nan, jnp.float32)
            replay_frac = jnp.zeros((), jnp.float32)
        self.mec_state, result = self.env.step(self.mec_state, tasks,
                                               decision, self._sp)
        self.metrics.update(result, tasks.active, read=self._pull)
        deadline = (self._sp.deadline_s if self._sp is not None
                    else self.env.params.deadline_s)
        self.telemetry = self._tel_update(self.telemetry, decision, result,
                                          tasks.active, deadline,
                                          replay_frac, loss)
        return tasks, self._pull(decision), result

    def _assignment(self, decision: np.ndarray, slot: int):
        """Decode one slot's decision into (replica name, exit layer)."""
        n, l = divmod(int(decision[slot]), self.env.L)
        return self.replicas[n].name, self.cfg.exit_layers[l]

    # ------------------------------------------------------------ hot-swap
    def set_scenario_params(self, sp: Optional[ScenarioParams]) -> None:
        """Hot-swap the MEC world model's numeric dynamics.

        ``sp`` is traced data in every compiled step, so switching
        scenarios mid-serving (say calm -> burst capacity regimes, or a
        ``ScenarioSpace`` draw) never triggers recompilation. ``None``
        restores the engine config's own knobs. Exit tables inside ``sp``
        must keep the engine's [N, L] shape.
        """
        if sp is not None:
            want = self.env.params.exit_times_s.shape
            got = jnp.shape(sp.exit_times_s)
            if got != want:
                raise ValueError(f"exit table shape {got} != engine {want}")
        self._sp = sp

    def get_agent_state(self) -> Optional[AgentState]:
        """The scheduler's live ``AgentState`` (params, opt state, replay
        ring, RNG, counters) — checkpoint it, train it offline in a
        ``RolloutDriver``, or inspect it. ``None`` without a scheduler."""
        return self.agent_state

    def set_agent_state(self, state: AgentState) -> None:
        """Hot-swap the scheduler's entire mutable state.

        Mirrors ``set_scenario_params``: the state is traced data in the
        compiled step, so swapping in a checkpointed or freshly-trained
        ``AgentState`` (same structure/shapes) never recompiles. Raises
        without a scheduler or on a structure mismatch.
        """
        if self.agent_def is None:
            raise ValueError("engine has no scheduler agent")
        want = jax.tree_util.tree_structure(self.agent_state)
        got = jax.tree_util.tree_structure(state)
        if want != got:
            raise ValueError(f"AgentState structure {got} != engine {want}")
        for a, b in zip(jax.tree_util.tree_leaves(self.agent_state),
                        jax.tree_util.tree_leaves(state)):
            if jnp.shape(a) != jnp.shape(b):
                raise ValueError(
                    f"AgentState leaf shape {jnp.shape(b)} != engine "
                    f"{jnp.shape(a)}")
        self.agent_state = state

    # ----------------------------------------------------------- telemetry
    def _extra_summary(self, summary: dict) -> None:
        """Hook: subclasses fold engine-specific summary keys in place."""

    def telemetry_snapshot(self) -> dict:
        """Host view of the request telemetry (one device->host pull).

        ``summary`` carries the derived headline numbers
        (``deadline_hit_rate``, ``latency_p50``/``latency_p99`` in
        deadline units plus ``latency_p50_s``/``latency_p99_s`` converted
        with the engine's configured deadline, decision shares, reward
        decomposition). ``latency_p50_s_exact``/``latency_p99_s_exact``
        are true order statistics over the exact last-K latency ring —
        the histogram estimates' ground truth. Before any request is
        served every quantile is ``None`` and every rate 0 (never NaN —
        the snapshot is strict-JSON as is). ``transfers`` counts the
        engine's host<->device round-trips; ``host_pulls`` is every
        blocking device->host read on the serving path, one per
        ``serve/pull`` span.
        """
        host = telemetry_host(self.telemetry)
        summary = telemetry_summary(host)
        dl = float(self.env.cfg.deadline_s)
        lat = host["hists"]["latency"]
        for q, key in ((0.5, "latency_p50_s"), (0.99, "latency_p99_s")):
            v = hist_quantile(lat["edges"], lat["counts"], q)
            summary[key] = float(v) * dl if np.isfinite(v) else None
        ring = np.asarray(self._latency_ring, np.float64)
        summary["latency_ring_n"] = int(ring.size)
        for q, key in ((50, "latency_p50_s_exact"),
                       (99, "latency_p99_s_exact")):
            summary[key] = (float(np.percentile(ring, q)) if ring.size
                            else None)
        summary["tokens_served"] = int(self.tokens_served)
        self._extra_summary(summary)
        host["summary"] = summary
        self.transfers["telemetry_pulls"] += 1
        host["transfers"] = dict(self.transfers)
        return host

    def make_request(self, prompt_len: int = 8, max_new: int = 8) -> Request:
        """Synthetic request for arrival-driven serving."""
        toks = self._req_rng.integers(0, self.cfg.vocab, prompt_len)
        return Request(tokens=toks.astype(np.int32),
                       deadline_s=self.env.cfg.deadline_s, max_new=max_new)


# ============================================================== sync engine
def make_decode_loop(cfg: ArchConfig, step, cache_len: int):
    """The greedy, teacher-forced decode of one exit group as one program.

    ``serve_steps(params, packed) -> tokens [b, cache_len]`` reads one
    int32 array ``packed [b, cache_len + 2]``: each row's prompt padded to
    ``cache_len``, then the prompt's length, then the group's position
    count ``total`` (the same in every row). It makes the group's cache
    and runs ``step`` at positions 0 .. total-1, the cache and the
    current token carried in place; the input at each next position is
    the prompt's next token while inside the prompt, else the argmax just
    produced. Column ``pos`` of the result is the argmax at ``pos``
    (columns from ``total`` on stay 0). The trip count is data, so one
    compiled program serves every group length at a given group size.
    """
    model = model_for(cfg)

    def serve_steps(params, packed):
        b = packed.shape[0]
        prompts = packed[:, :cache_len]
        lens = packed[:, cache_len]
        total = packed[0, cache_len + 1]

        def body(pos, carry):
            cache, cur, out = carry
            logits, cache = step(params, cache, cur,
                                 jnp.full((b,), pos, jnp.int32))
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)
            out = out.at[:, pos].set(nxt)
            col = jax.lax.dynamic_index_in_dim(prompts, pos + 1, axis=1,
                                               keepdims=False)
            return cache, jnp.where(pos + 1 < lens, col, nxt), out

        carry = (model.init_cache(cfg, b, cache_len), prompts[:, 0],
                 jnp.zeros((b, cache_len), jnp.int32))
        return jax.lax.fori_loop(0, total, body, carry)[2]

    return serve_steps


class EdgeServingEngine(_ServingCore):
    """The synchronous slot loop: one ``serve_slot`` call per MEC slot.

    Per-exit compiled LM decode loops live here (the exit choice is a
    compile-time schedule truncation); the scheduling decision itself
    already runs the batched actor program shared with the rollout and
    sweep layers.
    """

    def __init__(self, cfg: ArchConfig, replicas: list[Replica], **kw):
        kw.setdefault("init_model", True)
        super().__init__(cfg, replicas, **kw)
        # per exit (static): one decode step, and the whole greedy loop
        # over it as one program (compiled per group size)
        steps = ({e: make_serve_step(cfg, exit_layer=e)
                  for e in cfg.exit_layers} if self.model is not None
                 else {})
        self._steps = {e: jax.jit(f) for e, f in steps.items()}
        self._loops = {e: jax.jit(make_decode_loop(cfg, f, self.cache_len))
                       for e, f in steps.items()}

    # ------------------------------------------------------------- decoding
    def _decode(self, requests: list[Request], exit_layer: int) -> list:
        """Greedy-decode a batch at the given exit depth, under one
        ``serve/decode`` span.

        The prompts, their lengths and the position count go up in one
        explicit host->device upload (``decode_h2d``), the exit's decode
        loop (``make_decode_loop``) runs every position as one launched
        program (``decode_launches``), and the generated tokens come back
        in one device->host read (``decode_d2h``, and a ``serve/pull``).
        Raises ``ValueError`` when the group needs more positions than
        the engine's ``cache_len``.
        """
        with span("serve/decode"):
            b = len(requests)
            prompts = [np.asarray(r.tokens, np.int32) for r in requests]
            lens = np.array([len(p) for p in prompts], np.int32)
            total = int(lens.max()) + max(r.max_new for r in requests)
            if total > self.cache_len:
                raise ValueError(
                    f"decode group needs {total} positions > cache_len "
                    f"{self.cache_len}")
            packed = np.zeros((b, self.cache_len + 2), np.int32)
            for i, p in enumerate(prompts):
                packed[i, : len(p)] = p
            packed[:, self.cache_len] = lens
            packed[:, self.cache_len + 1] = total
            packed = jax.device_put(packed)    # the one upload
            self.transfers["decode_h2d"] += 1
            gen = self._loops[exit_layer](self.params, packed)
            self.transfers["decode_launches"] += 1
            gen = self._pull(gen)              # the one read
            self.transfers["decode_d2h"] += 1
        # request i's outputs are the argmaxes at positions
        # len(p)-1 .. len(p)-1+max_new-1
        return [[int(t) for t in
                 gen[i, lens[i] - 1: lens[i] - 1 + r.max_new]]
                for i, r in enumerate(requests)]

    # -------------------------------------------------------------- serving
    def serve_slot(self, requests: Optional[list[Request]] = None, *,
                   decode: bool = False):
        """Schedule one slot of requests; optionally run real decoding.

        With ``requests=None`` the slot's load is arrival-driven: the
        workload generator's ``active`` mask (Poisson/MMPP per
        ``MECConfig.workload``) decides which batch slots carry a request,
        each synthesized by ``make_request`` (the generated requests come
        back under ``info["requests"]``). Returns (assignments, info) with
        one ``(replica, exit_layer)`` per request.
        """
        active = None
        slot_ids: Optional[list] = None
        if requests is not None:
            assert len(requests) <= self.batch_slots
            slot_ids = list(range(len(requests)))
            if self.env.cfg.workload != "iid":
                # explicit requests ARE the arrivals: align the simulated
                # mask so metrics/assignments describe the real requests,
                # not the generator's draw
                active = np.zeros((self.batch_slots,), np.float32)
                active[: len(requests)] = 1.0
        with span("serve/price"):
            tasks, decision, result = self._price_slot(active)
            if requests is None:
                act = np.flatnonzero(np.asarray(tasks.active) > 0.5)
                slot_ids = [int(i) for i in act]
                requests = [self.make_request() for _ in slot_ids]
            # exact per-request latencies for the last-K ring (finished
            # requests only; inf = unreachable link is a miss, not a
            # time). ``tasks.active`` was read by the metrics update:
            # its host copy is reused here, not read again.
            tt = np.asarray(self._pull(result.t_total), np.float64)
            act_mask = np.asarray(tasks.active, np.float64) > 0.5
            self._latency_ring.extend(
                tt[act_mask & np.isfinite(tt)].tolist())

            assignments = [self._assignment(decision, slot)
                           for slot in slot_ids]
            self.tokens_served += sum(r.max_new for r in requests)
        texts = None
        if decode:
            by_exit = {}
            for i, (_, e) in enumerate(assignments):
                by_exit.setdefault(e, []).append(i)
            texts = [None] * len(requests)
            for e, idxs in by_exit.items():
                outs = self._decode([requests[i] for i in idxs], e)
                for i, o in zip(idxs, outs):
                    texts[i] = o
        return assignments, {"reward": float(result.reward),
                             "n_requests": len(requests),
                             "requests": requests,
                             "texts": texts}


# ===================================================== pure scheduler core
class RunningReq(NamedTuple):
    """One batch slot's occupant, from admission to release.

    ``hold`` is the number of decode steps the request still occupies
    its slot (filled after the pricing decision); ``latency_s`` is the
    realized MEC service latency (inf = unreachable link, NaN before the
    decision); ``replica``/``exit_layer`` record the assignment;
    ``variant`` tags which A/B agent variant priced it (empty without a
    pool).
    """
    entry: QueueEntry
    admitted_s: float
    hold: int = 0
    latency_s: float = float("nan")
    replica: str = ""
    exit_layer: int = -1
    variant: str = ""


class BatchState(NamedTuple):
    """Fixed-capacity batch occupancy: one ``RunningReq`` or None per
    slot. Capacity is structural (the tuple length), so occupancy can
    never exceed it by construction — the invariant the tests assert."""
    slots: Tuple[Optional[RunningReq], ...]


class SchedEvents(NamedTuple):
    """What one pure scheduler tick decided."""
    expired: Tuple[QueueEntry, ...]            # dropped past-deadline
    admitted: Tuple[Tuple[int, QueueEntry], ...]  # (slot, entry) pairs


def batch_init(capacity: int) -> BatchState:
    if capacity < 1:
        raise ValueError(f"batch needs >= 1 slot, got {capacity}")
    return BatchState(slots=(None,) * capacity)


def batch_occupancy(batch: BatchState) -> int:
    return sum(1 for s in batch.slots if s is not None)


def sched_tick(queue: QueueState, batch: BatchState, now: float):
    """The pure admit/expire step: a function of (queue, batch, clock).

    Expires every pending request whose deadline has passed, then admits
    the best (priority, seq)-ordered schedulable requests into the
    lowest free slots. No device work, no wall clock, no hidden state —
    every decision the async engine makes about *which* requests run is
    taken here and unit-testable in isolation. Returns
    (queue', batch', SchedEvents).
    """
    queue, expired = queue_expire(queue, now)
    free = [i for i, s in enumerate(batch.slots) if s is None]
    queue, entries = queue_pop(queue, len(free), now)
    slots = list(batch.slots)
    admitted = []
    for slot, entry in zip(free, entries):
        slots[slot] = RunningReq(entry=entry, admitted_s=now)
        admitted.append((slot, entry))
    return (queue, BatchState(slots=tuple(slots)),
            SchedEvents(expired=tuple(e for e in expired),
                        admitted=tuple(admitted)))


def sched_evict(queue: QueueState, batch: BatchState,
                slot_ids: Iterable[int]):
    """Preempt running slots back into the queue (pure).

    Evicted entries keep their original submission seq, so the next
    ``sched_tick`` re-admits them in exactly the order they originally
    held — evict-then-readmit is idempotent on the schedule. Returns
    (queue', batch', evicted entries).
    """
    slots = list(batch.slots)
    evicted = []
    for i in sorted(set(slot_ids)):
        running = slots[i]
        if running is None:
            continue
        evicted.append(running.entry)
        slots[i] = None
    queue = queue_requeue(queue, evicted)
    return queue, BatchState(slots=tuple(slots)), tuple(evicted)


def batch_release(batch: BatchState):
    """Advance every occupied slot by one decode step (pure).

    Decrements holds; slots whose hold reaches zero release their
    request (it finished decoding). Returns
    (batch', released (slot, RunningReq) pairs).
    """
    slots = list(batch.slots)
    released = []
    for i, running in enumerate(slots):
        if running is None:
            continue
        hold = running.hold - 1
        if hold <= 0:
            released.append((i, running))
            slots[i] = None
        else:
            slots[i] = running._replace(hold=hold)
    return BatchState(slots=tuple(slots)), tuple(released)


# ================================================================ A/B pool
class AgentPool:
    """Live A/B over hot-swappable agent variants (round-robin).

    Each engine step checks one variant out (``set_agent_state``), runs
    it, and checks the updated state back in — variants keep learning
    independently while serving interleaved traffic, and per-variant
    served/hit counters make the comparison readable. Deterministic: the
    schedule is a pure function of the step index.
    """

    def __init__(self, variants: dict):
        if not variants:
            raise ValueError("AgentPool needs at least one variant")
        self.variants = dict(variants)
        self._order = tuple(self.variants)
        self.stats = {name: {"steps": 0, "served": 0, "hits": 0}
                      for name in self._order}

    def pick(self, step_idx: int) -> str:
        return self._order[step_idx % len(self._order)]

    def record(self, variant: str, *, served: int, hits: int) -> None:
        st = self.stats[variant]
        st["served"] += served
        st["hits"] += hits


# ============================================================= async engine
class ContinuousServingEngine(_ServingCore):
    """Async, continuously-batched serving on the shared world model.

    Requests enter via ``submit`` (e.g. a ``serve.loadgen`` trace) into
    the deadline-aware queue; every ``step`` is one decode step: the
    pure scheduler core admits into free slots and expires dead pending
    requests, ONE batched GRLE actor program prices the whole batch
    (amortized over ``batch_slots`` requests — no per-exit recompiles),
    the MEC world model realizes latencies, and finished slots release
    for the next step's admissions.

    ``hold`` picks the slot-occupancy model: ``"slot"`` (default)
    releases a request after its decision step — the same semantics as
    the synchronous ``serve_slot``, which is what makes the two engines
    decision-equivalent on a shared trace; ``"latency"`` holds each slot
    for ceil(latency / slot_s) steps, modeling multi-step decode
    occupancy with continuous backfill.

    Driven by an explicit ``clock`` (default ``VirtualClock``): the
    engine advances it by ``slot_s`` per step, so the whole loop —
    admissions, expiries, decisions, telemetry — is a deterministic pure
    function of (seed, trace). Counter law, kept exactly:
    ``admitted == served + expired + in_flight``.
    """

    def __init__(self, cfg: ArchConfig, replicas: list[Replica], *,
                 batch_slots: int = 32, clock=None, hold: str = "slot",
                 **kw):
        if hold not in ("slot", "latency"):
            raise ValueError(f"unknown hold policy {hold!r}")
        kw.setdefault("init_model", False)
        kw.setdefault("workload", "mmpp")
        super().__init__(cfg, replicas, batch_slots=batch_slots, **kw)
        self.clock = clock if clock is not None else VirtualClock()
        self.hold = hold
        self.queue = queue_init()
        self.batch = batch_init(batch_slots)
        self.pool: Optional[AgentPool] = None
        # exact host-side request accounting (ints — the balance law is
        # asserted exactly); telemetry mirrors these on-device for
        # history/snapshot plumbing
        self.counts = {"admitted": 0, "served": 0, "expired": 0, "hits": 0}
        self._step_idx = 0
        self._tel_admit_delta = 0      # submits not yet folded on-device
        self._serve_tel = jax.jit(serve_telemetry_update)

    def _make_telemetry(self):
        return serve_telemetry(self.env.N, self.env.L)

    # ------------------------------------------------------------ occupancy
    @property
    def in_flight(self) -> int:
        """Requests inside the system: pending + occupying batch slots."""
        return queue_depth(self.queue) + batch_occupancy(self.batch)

    def set_agent_pool(self, pool: Optional[AgentPool]) -> None:
        """Attach (or detach with None) a live A/B variant pool."""
        if pool is not None and self.agent_def is None:
            raise ValueError("engine has no scheduler agent to A/B")
        self.pool = pool

    # -------------------------------------------------------------- intake
    def submit(self, requests: Iterable[ServeRequest]) -> int:
        """Accept requests into the queue; returns how many."""
        reqs = list(requests)
        self.queue = queue_push(self.queue, reqs)
        self.counts["admitted"] += len(reqs)
        self._tel_admit_delta += len(reqs)
        return len(reqs)

    # ---------------------------------------------------------------- step
    def _hold_steps(self, latency_s: float) -> int:
        if self.hold == "slot" or not math.isfinite(latency_s):
            return 1
        return max(1, int(math.ceil(latency_s / self.env.cfg.slot_s)))

    def step(self) -> dict:
        """One decode step; returns a JSON-safe report of what happened.

        Order inside the step: (1) pure scheduler tick — expire dead
        pending requests, admit into free slots; (2) one batched pricing
        decision over the occupancy mask (newly admitted slots are the
        active ones; held slots keep decoding and are inactive); (3)
        realized latencies fill the admitted slots' holds/assignments;
        (4) holds advance and finished slots release as served; (5) the
        clock advances one ``slot_s``.
        """
        now = self.clock.now()
        variant = ""
        if self.pool is not None:
            variant = self.pool.pick(self._step_idx)
            self.set_agent_state(self.pool.variants[variant])
            self.pool.stats[variant]["steps"] += 1
        self.queue, self.batch, events = sched_tick(self.queue, self.batch,
                                                    now)
        self.counts["expired"] += len(events.expired)

        active = np.zeros((self.batch_slots,), np.float32)
        for slot, _ in events.admitted:
            active[slot] = 1.0
        with span("serve/price"):
            _, decision, result = self._price_slot(active)
            t_total = np.asarray(self._pull(result.t_total), np.float64)

        # fill the admitted slots: assignment, realized latency, hold
        slots = list(self.batch.slots)
        assignments = []
        for slot, entry in events.admitted:
            replica, exit_layer = self._assignment(decision, slot)
            latency = float(t_total[slot])
            slots[slot] = slots[slot]._replace(
                hold=self._hold_steps(latency), latency_s=latency,
                replica=replica, exit_layer=exit_layer, variant=variant)
            assignments.append({"rid": entry.req.rid, "slot": slot,
                                "replica": replica, "exit": exit_layer})
        self.batch = BatchState(slots=tuple(slots))

        self.batch, released = batch_release(self.batch)
        served = []
        for slot, running in released:
            req = running.entry.req
            # queue wait + realized service latency, against the absolute
            # deadline the request arrived with
            total = ((running.admitted_s - req.arrival_s)
                     + running.latency_s)
            hit = (math.isfinite(total)
                   and req.arrival_s + total <= req.deadline_s)
            self.counts["served"] += 1
            self.counts["hits"] += int(hit)
            self.tokens_served += req.max_new
            if math.isfinite(total):
                self._latency_ring.append(float(total))
            if self.pool is not None and running.variant:
                self.pool.record(running.variant, served=1, hits=int(hit))
            served.append({"rid": req.rid, "slot": slot, "hit": bool(hit),
                           "latency_s": (round(total, 9)
                                         if math.isfinite(total) else None),
                           "replica": running.replica,
                           "exit": running.exit_layer})
        if self.pool is not None:
            self.pool.variants[variant] = self.agent_state

        depth = queue_depth(self.queue)
        # device mirror of the host counts: "admitted" is requests
        # accepted into the system (submits since the last step), so the
        # admitted == served + expired + in-flight law reads identically
        # from either view
        self.telemetry = self._serve_tel(
            self.telemetry, self._tel_admit_delta, len(served),
            len(events.expired), depth)
        self._tel_admit_delta = 0
        report = {
            "step": self._step_idx,
            "now": round(now, 9),
            "admitted": [e.req.rid for _, e in events.admitted],
            "expired": [e.req.rid for e in events.expired],
            "assignments": assignments,
            "served": served,
            "queue_depth": depth,
            "occupancy": batch_occupancy(self.batch),
            "variant": variant or None,
        }
        self._step_idx += 1
        self.clock.advance(self.env.cfg.slot_s)
        return report

    # ----------------------------------------------------------------- run
    def run(self, trace: Iterable[ServeRequest], *,
            max_steps: Optional[int] = None, on_step=None) -> list:
        """Drive the engine over an arrival trace until drained.

        Requests are submitted when the clock reaches their
        ``arrival_s``; the loop steps until every request is served or
        expired (or ``max_steps``). ``on_step(engine, report)`` runs
        after each step — hot-swap hooks (``set_agent_state``,
        ``set_scenario_params``) are safe mid-trace. Returns the list of
        step reports (JSON-safe, byte-identical across replays under a
        ``VirtualClock``).
        """
        pending = sorted(trace, key=lambda r: (r.arrival_s, r.rid))
        i, n = 0, len(pending)
        reports = []
        while True:
            now = self.clock.now()
            while i < n and pending[i].arrival_s <= now:
                j = i
                while j < n and pending[j].arrival_s <= now:
                    j += 1
                self.submit(pending[i:j])
                i = j
            if i >= n and self.in_flight == 0:
                break
            if max_steps is not None and len(reports) >= max_steps:
                break
            report = self.step()
            reports.append(report)
            if on_step is not None:
                on_step(self, report)
        return reports

    # ------------------------------------------------------------ snapshot
    def _extra_summary(self, summary: dict) -> None:
        qd = telemetry_host(self.telemetry)["hists"]["queue_depth"]
        for q, key in ((0.5, "queue_depth_p50"), (0.99, "queue_depth_p99")):
            v = hist_quantile(qd["edges"], qd["counts"], q)
            summary[key] = float(v) if np.isfinite(v) else None
        served = self.counts["served"]
        summary.update(
            requests_admitted=self.counts["admitted"],
            requests_served=served,
            requests_expired=self.counts["expired"],
            requests_in_flight=self.in_flight,
            deadline_hit_rate_exact=(self.counts["hits"] / served
                                     if served else 0.0),
            steps=self._step_idx,
        )
