"""Shared rollout machinery for the paper-figure benchmarks."""
from __future__ import annotations

import json
import os
import time

import jax
import numpy as np

from repro.core import make_agent
from repro.mec import MECEnv, RunningMetrics, make_scenario
from repro.obs.history import default_store, history_manifest
from repro.obs.log import git_rev

METHODS = ("grle", "grl", "drooe", "droo")
RESULTS_DIR = os.environ.get("REPRO_RESULTS", "results")

# Row keys that are labels/counts, not measurements — excluded from the
# metric set a history record carries.
NON_METRIC_KEYS = ("backend", "n_jax_devices", "git_rev", "packs",
                   "cells", "compiled_programs")


def timed(fn, *args, **kwargs):
    """Run ``fn`` and return (result, wall seconds).

    THE timing helper for every benchmark: the clock stops only after
    ``jax.block_until_ready`` on the result, so async dispatch can't
    make a path look faster than the device work it queued. Use a
    monotonic wall clock (``perf_counter``), never ``time.time``.
    Rows measured with it and written via ``save_rows``/
    ``merge_bench_rows`` are stamped (backend, jax device count, git
    rev) and appended to the run-history store automatically.
    """
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


def rollout_method(method: str, scenario: str, *, n_devices: int,
                   slot_ms: float, slots: int, seed: int = 0):
    cfg = make_scenario(scenario, n_devices=n_devices, slot_ms=slot_ms)
    env = MECEnv(cfg)
    key = jax.random.PRNGKey(seed)
    agent = make_agent(method, env, key, seed=seed)
    metrics = RunningMetrics(slot_s=cfg.slot_s)

    def episode():
        state = env.reset()
        k = key
        for _ in range(slots):
            k, sk = jax.random.split(k)
            tasks = env.sample_slot(sk)
            dec, _ = agent.act(state, tasks)
            state, res = env.step(state, tasks, dec)
            metrics.update(res, tasks.active)
        return state

    _, wall_s = timed(episode)
    out = metrics.summary()
    out.update(method=method, scenario=scenario, n_devices=n_devices,
               slot_ms=slot_ms, slots=slots, wall_s=round(wall_s, 1))
    return out


def sweep_methods(scenario: str, *, device_counts, slot_lengths_ms, slots,
                  seed=0, methods=METHODS):
    rows = []
    for method in methods:
        for m in device_counts:
            for tau in slot_lengths_ms:
                row = rollout_method(method, scenario, n_devices=m,
                                     slot_ms=tau, slots=slots, seed=seed)
                rows.append(row)
                print(f"  {method:6s} M={m:3d} tau={tau:4.0f}ms  "
                      f"acc={row['avg_accuracy']:.3f} ssp={row['ssp']:.3f} "
                      f"thr={row['throughput_tps']:.1f}/s", flush=True)
    return rows


def stamp_rows(rows) -> list:
    """Stamp every row with where it was measured: jax backend, jax
    device count (``n_jax_devices`` — ``n_devices`` already means IoT
    devices M in the paper rows) and git revision. History comparisons
    filter on these, so a laptop number never gates a TPU trend."""
    backend = jax.default_backend()
    n_dev = jax.device_count()
    rev = git_rev()
    for row in rows:
        row.setdefault("backend", backend)
        row.setdefault("n_jax_devices", n_dev)
        row.setdefault("git_rev", rev)
    return rows


def _row_label(name: str, row: dict) -> str:
    """A stable history name for one row: its own ``name`` if present,
    else the module/method-M-tau label the CSV digest uses."""
    if row.get("name"):
        return str(row["name"])
    return (f"{name}/{row.get('method', 'row')}-M{row.get('n_devices', '')}"
            f"-t{row.get('slot_ms', '')}")


def record_rows(name: str, rows, *, history=None) -> None:
    """Append one manifest-stamped ``bench`` history record per row.

    ``history=None`` uses the env-configured store (``REPRO_HISTORY``,
    default ``results/history``; empty string disables). The record's
    metric set is every finite numeric row entry except the provenance
    stamps, so any measurement key (``us_per_call``, ``steps_per_s``,
    ``flops``, ...) lands in the trend automatically.
    """
    store = history if history is not None else default_store()
    if store is None:
        return
    manifest = history_manifest()
    for row in rows:
        metrics = {k: v for k, v in row.items()
                   if k not in NON_METRIC_KEYS
                   and isinstance(v, (int, float))
                   and not isinstance(v, bool) and np.isfinite(v)}
        if not metrics:
            continue
        store.append("bench", _row_label(name, row), metrics,
                     manifest=manifest,
                     derived=row.get("derived", ""))


def save_rows(name: str, rows, *, history=None) -> str:
    """Write ``results/<name>.json`` and append the rows to run history."""
    stamp_rows(rows)
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, f"{name}.json")
    with open(path, "w") as f:
        json.dump(rows, f, indent=1)
    record_rows(name, rows, history=history)
    return path


def merge_bench_rows(path: str, new_rows) -> None:
    """Refresh only the rows whose names ``new_rows`` re-measured,
    preserving every other row of the committed BENCH_*.json; the
    re-measured rows also append to run history."""
    stamp_rows(new_rows)
    names = {r["name"] for r in new_rows}
    kept = []
    if os.path.exists(path):
        with open(path) as f:
            kept = [r for r in json.load(f) if r.get("name") not in names]
    with open(path, "w") as f:
        json.dump(kept + new_rows, f, indent=1)
    base = os.path.splitext(os.path.basename(path))[0]
    record_rows(base, new_rows)


def assert_two_compile_packs(scenarios: str, seeds: int, *, n_devices=4,
                             n_slots=20, replay_capacity=16, batch_size=4,
                             train_every=5):
    """The compile-count acceptance guard, shared by the sweep and actor
    benchmarks: a full 4-method x seeds x scenarios grid must pack into
    exactly 2 compiled programs (one per actor family — exit masks and
    scenario knobs are agent-state data). Executes both packs twice and
    pins one compile per program.
    Returns (packs, cells)."""
    from repro.sweep import SweepSpec, pack_cells
    from repro.sweep.runner import PackProgram

    spec = SweepSpec.from_names(scenarios, "grle,grl,drooe,droo", seeds,
                                n_devices=n_devices, n_slots=n_slots,
                                replay_capacity=replay_capacity,
                                batch_size=batch_size,
                                train_every=train_every)
    cells = spec.expand()
    packs = pack_cells(cells)
    assert len(packs) == 2, [p.label() for p in packs]
    assert {p.family for p in packs} == {"gcn", "mlp"}
    k = len(spec.scenarios)
    assert sum(len(p.cells) for p in packs) == len(cells) == 4 * seeds * k
    # CompileTracker owns both measurement levels: exact per-program
    # cache pins plus the process-wide compile-event stream for logging
    from repro.obs import CompileTracker
    with CompileTracker() as ct:
        for pack in packs:
            prog = PackProgram(pack)
            prog.run()
            prog.run()             # warm re-run must reuse the cache
            ct.track(pack.label(), prog._episode)
    ct.assert_counts({pack.label(): 1 for pack in packs})
    return packs, cells


def print_csv(name: str, rows, keys) -> None:
    print(f"# {name}")
    print(",".join(["name"] + list(keys)))
    for r in rows:
        label = f"{name}/{r.get('method', '')}-M{r.get('n_devices', '')}" \
                f"-t{r.get('slot_ms', '')}"
        print(",".join([label] + [f"{r.get(k, '')}" for k in keys]))
