"""Execute sweep cells: packed + sharded by default, per-cell as reference.

``run_pack`` is the mega-batch path: one template env/``AgentDef``/driver
per pack (the traced structure), per-cell ``AgentState``s — built with
``jax.vmap(def_.init)`` over the cell axis [C], each cell's exit mask
swapped in as data — plus per-cell RNG streams and ``ScenarioParams``
batched along the same axis, the whole episode vmapped over [C] inside
one ``lax.scan`` and sharded over available devices (``sharding.fleet``;
a 1-device host runs the identical program without the placement).
Because both scenario knobs *and* the exit mask are agent-state data,
one pack mixes scenarios and methods of one actor family — a 4-method x
S-seed x K-scenario grid is 2 compiles total. Per-cell metrics come from
the driver's device-resident accumulator, so the only host transfer is a
handful of scalars per cell at the very end.

``run_cell`` is the sequential reference: an ordinary ``RolloutDriver``
run for one cell, sharing the exact seed derivation (``cell_keys``) —
used by the equivalence tests and as the baseline in
``benchmarks/sweep_throughput.py``. Units in result rows: accuracies and
SSP are fractions in [0, 1], ``throughput_tps`` is successful tasks per
second per fleet, times are seconds.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.policy import AgentDef, agent_def
from repro.mec.env import MECEnv
from repro.mec.scenarios import resolve_scenario
from repro.obs.log import json_safe
from repro.obs.telemetry import telemetry_host, telemetry_summary
from repro.rollout.driver import (RolloutDriver, carry_metrics,
                                  carry_telemetry)
from repro.rollout.metrics import metrics_finalize
from repro.sharding.fleet import (map_leading_axis, pad_to_devices,
                                  shard_leading_axis)
from repro.sweep.packer import Pack, pack_cells
from repro.sweep.spec import Cell, SweepSpec, cell_keys
from repro.sweep.store import SweepStore


def _resolve_cell(cell: Cell):
    """(env, sp): the cell's env plus its sampled ``ScenarioParams`` —
    None for named scenarios (the env's own params apply), the
    deterministic draw for ``space:`` cells."""
    cfg, sp = resolve_scenario(cell.scenario, n_devices=cell.n_devices,
                               slot_ms=cell.slot_ms,
                               **dict(cell.overrides))
    return MECEnv(cfg), sp


def _scenario_env(cell: Cell) -> MECEnv:
    return _resolve_cell(cell)[0]


def _cell_def(cell: Cell, env: MECEnv, *, method: Optional[str] = None,
              actor: Optional[str] = None,
              use_pallas: Optional[bool] = None) -> AgentDef:
    """The cell's agent spec; ``actor=`` builds the pack-template def
    (family only — per-cell exit masks are swapped in as state data)."""
    kw = dict(buffer_size=cell.replay_capacity, batch_size=cell.batch_size,
              train_every=cell.train_every, use_pallas=use_pallas)
    if actor is not None:
        return AgentDef(env=env, actor=actor, **kw)
    return agent_def(method or cell.method, env, **kw)


def _finish_row(row: dict, cell: Cell) -> dict:
    row["tasks"] = int(row["tasks"])
    row["train_steps"] = int(row["train_steps"])
    if row["final_loss"] is not None and not np.isfinite(row["final_loss"]):
        row["final_loss"] = None
    row.update(scenario=cell.scenario, method=cell.method, seed=cell.seed,
               cell=cell.cell_hash)
    return row


# ------------------------------------------------------------------ packed
class PackProgram:
    """One pack's compiled episode + its batched inputs.

    Construction builds the template def/driver, per-cell ``AgentState``s
    and the jitted episode; ``run()`` executes it. Re-running the same
    program reuses the compile cache, so a second ``run()`` is the
    steady-state (resumed sweep) rate — which is what
    ``benchmarks/sweep_throughput.py`` times as ``packed_warm``.
    """

    def __init__(self, pack: Pack, *, mesh=None,
                 use_pallas: Optional[bool] = None,
                 telemetry: bool = False):
        self.pack = pack
        cells = list(pack.cells)
        ref = cells[0]
        env = _scenario_env(ref)
        adef = _cell_def(ref, env, actor=pack.family, use_pallas=use_pallas)
        drv = RolloutDriver(adef, n_fleets=ref.n_fleets,
                            telemetry=telemetry)
        self._env = env
        self._telemetry = telemetry

        pkeys = jnp.stack([cell_keys(c)[0] for c in cells])
        rkeys = jnp.stack([cell_keys(c)[1] for c in cells])
        # per-cell exit masks (GRLE vs GRL, DROOE vs DROO) are AgentState
        # data — methods of one family differ only by state
        masks = jnp.stack([_cell_def(c, env).exit_mask() for c in cells])
        # each cell's scenario knobs, stacked along the cell axis — this
        # is what lets one compiled episode serve a mixed-scenario pack
        # (space-draw cells contribute their sampled params)
        def cell_params(c):
            env_c, sp = _resolve_cell(c)
            return sp if sp is not None else env_c.params

        sps = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *[cell_params(c) for c in cells])

        # pad the cell axis up to the device count (results discarded)
        n_real = len(cells)
        n_pad = pad_to_devices(n_real, mesh) - n_real
        if n_pad:
            rep = lambda x: jnp.concatenate(
                [x, jnp.repeat(x[-1:], n_pad, axis=0)], axis=0)
            pkeys, rkeys, masks = rep(pkeys), rep(rkeys), rep(masks)
            sps = jax.tree_util.tree_map(rep, sps)

        states = jax.vmap(
            lambda k, m: adef.init(k)._replace(exit_mask=m))(pkeys, masks)
        carries = jax.vmap(
            lambda k, st, s: drv.init_carry(k, agent_state=st, sp=s))(
            rkeys, states, sps)
        self._carries, self._sps = shard_leading_axis((carries, sps), mesh)

        def run_cells(cs, ss):
            def step(c, _):
                new_c, _ = jax.vmap(drv._slot)(c, ss)
                return new_c, None

            return jax.lax.scan(step, cs, None, length=ref.n_slots)[0]

        run_cells = map_leading_axis(run_cells, mesh)

        def episode(cs, ss):
            final = run_cells(cs, ss)
            fin = jax.vmap(lambda m: metrics_finalize(
                m, slot_s=env.cfg.slot_s,
                n_fleets=ref.n_fleets))(final.metrics)
            # cell-stacked registry rides out with the scalar rows — the
            # telemetry leg still costs one host transfer per pack
            return fin, final.telemetry

        self._episode = jax.jit(episode)

    def run(self) -> list:
        """Execute the episode; one metrics row per cell, in pack order."""
        metrics, tel = self._episode(self._carries, self._sps)
        metrics = {k: np.asarray(v) for k, v in metrics.items()}
        tel = jax.device_get(tel)  # [C]-stacked registry, one transfer
        rows = []
        for i, cell in enumerate(self.pack.cells):
            row = {k: float(v[i]) for k, v in metrics.items()}
            if tel is not None:
                host = telemetry_host(tel, index=i)
                host["summary"] = telemetry_summary(host)
                row["telemetry"] = json_safe(host)
            rows.append(_finish_row(row, cell))
        return rows


def run_pack(pack: Pack, *, mesh=None,
             use_pallas: Optional[bool] = None,
             telemetry: bool = False) -> list:
    """Run every cell of a pack in one vmapped (optionally sharded) episode.

    Returns one metrics row per cell, in pack order. ``telemetry=True``
    attaches each cell's registry snapshot + summary under
    ``row["telemetry"]`` (JSON-safe).
    """
    return PackProgram(pack, mesh=mesh, use_pallas=use_pallas,
                       telemetry=telemetry).run()


# -------------------------------------------------------------- sequential
def run_cell(cell: Cell, *, use_pallas: Optional[bool] = None,
             telemetry: bool = False) -> dict:
    """One cell through a plain ``RolloutDriver`` (reference/baseline)."""
    env, sp = _resolve_cell(cell)
    pkey, rkey = cell_keys(cell)
    adef = _cell_def(cell, env, use_pallas=use_pallas)
    drv = RolloutDriver(adef, n_fleets=cell.n_fleets, telemetry=telemetry)
    # sp is None for named scenarios (byte-identical legacy path); a
    # space cell's draw rides in as shared-across-fleets traced data
    carry, _ = drv.run(rkey, cell.n_slots, mode="scan",
                       agent_state=adef.init(pkey), sp=sp)
    row = carry_metrics(carry, slot_s=env.cfg.slot_s,
                        n_fleets=cell.n_fleets)
    if telemetry:
        row["telemetry"] = json_safe(carry_telemetry(carry))
    return _finish_row(row, cell)


# ------------------------------------------------------------------- sweep
def run_sweep(spec: SweepSpec, *, store: Optional[SweepStore] = None,
              mesh=None, packed: bool = True, log=print,
              use_pallas: Optional[bool] = None,
              telemetry: bool = False, history=None) -> list:
    """Run the whole grid; returns rows in ``spec.expand()`` order.

    With a store, finished cells are loaded instead of recomputed and
    never rewritten. The execution unit is the *pack*: a pack runs iff
    any member cell is missing (pack composition depends only on the
    grid, so a resumed sweep recomputes missing cells inside the exact
    same vmapped batch it would have run the first time).

    ``history`` (a ``repro.obs.HistoryStore``) appends one
    manifest-stamped ``sweep`` record per *executed* cell — cached rows
    were recorded by the run that produced them. The record carries the
    cell's scalar metrics plus (with ``telemetry=True``) the telemetry
    summary's scalar headline numbers.
    """
    cells = spec.expand()
    packs = pack_cells(cells)
    results: dict = {}
    for pack in packs:
        missing = [c for c in pack.cells
                   if store is None or not store.has(c)]
        for c in pack.cells:
            if c not in missing:
                results[c] = store.load(c)
        if not missing:
            log(f"  [sweep] {pack.label()}: all "
                f"{len(pack.cells)} cells cached")
            continue
        log(f"  [sweep] {pack.label()}: running "
            f"({len(pack.cells) - len(missing)} cached)")
        # defaults are omitted so monkeypatched/legacy runners with the
        # pre-switch signature keep working
        kw = {} if use_pallas is None else {"use_pallas": use_pallas}
        if telemetry:
            kw["telemetry"] = True
        if packed:
            # the whole pack runs (one compiled episode), but cached cells
            # keep their stored rows — never recomputed results
            rows = run_pack(pack, mesh=mesh, **kw)
            pairs = [(c, row) for c, row in zip(pack.cells, rows)
                     if c in missing]
        else:
            # per-cell runs are independent: execute only the missing ones
            pairs = [(c, run_cell(c, **kw)) for c in missing]
        for c, row in pairs:
            results[c] = row
            if store is not None:
                store.save(c, row)
            if history is not None:
                _append_history(history, c, row, use_pallas=use_pallas)
    return [results[c] for c in cells]


def _append_history(history, cell: Cell, row: dict, *,
                    use_pallas: Optional[bool] = None) -> dict:
    """One ``sweep`` history record for an executed cell's row."""
    from repro.obs.history import history_manifest

    metrics = {k: v for k, v in row.items()
               if k != "seed"  # label (already in the record name)
               and isinstance(v, (int, float)) and not isinstance(v, bool)
               and np.isfinite(v)}
    tel = row.get("telemetry") or {}
    for k, v in (tel.get("summary") or {}).items():
        if isinstance(v, (int, float)) and not isinstance(v, bool) \
                and np.isfinite(v):
            metrics[f"tel_{k}"] = v
    cfg, _ = resolve_scenario(cell.scenario, n_devices=cell.n_devices,
                              slot_ms=cell.slot_ms, **dict(cell.overrides))
    return history.append(
        "sweep", f"{cell.scenario}/{cell.method}/s{cell.seed}", metrics,
        manifest=history_manifest(config_signature=cfg.static_signature(),
                                  use_pallas=use_pallas),
        cell=cell.cell_hash, n_slots=cell.n_slots)
