"""Observability layer: device-resident telemetry, compile tracking,
profiler hooks, structured run logs.

Four legs (see docs/ARCHITECTURE.md "Observability layer"):

  telemetry — ``Telemetry`` registry pytree (named counters +
              fixed-bucket histograms) carried through the rollout scan;
              one host transfer per episode/pack
  compile   — ``CompileTracker``: jax.monitoring compile events + exact
              per-jit-function compile-count pins (the pack guards)
  profile   — opt-in ``jax.profiler`` trace capture, ``phase`` scopes
              around actor/critic/env/train, and the serving engine's
              host spans: ``serve/price`` and ``serve/decode`` per layer
              of a call, ``serve/pull`` per device->host read (``pull``)
  log       — JSONL run logs (manifest with config signature + git rev,
              per-episode telemetry snapshots, bench rows), NaN-safe
  history   — append-only cross-run record store (``results/history/``),
              manifest-stamped for apples-to-apples comparison; fed by
              benchmark rows, sweep cells and population generations
              (the serve snapshot does not feed it)
  regress   — noise-aware (median/MAD) perf-regression verdicts over
              the history store, the CI sentinel's engine
  cost      — static FLOPs/bytes/arithmetic-intensity attribution for
              the hot compiled programs (driver step, sweep pack,
              serve decode)
"""
from repro.obs.telemetry import (
    Histogram,
    Telemetry,
    hist_add,
    hist_init,
    hist_quantile,
    hist_to_host,
    rollout_telemetry,
    telemetry_host,
    telemetry_init,
    telemetry_summary,
    telemetry_update,
)
from repro.obs.compile import CompileTracker
from repro.obs.profile import phase, pull, span, trace_capture
from repro.obs.log import RunLog, json_safe, read_events, run_manifest
from repro.obs.history import (HistoryStore, default_store,
                               history_manifest)
from repro.obs.regress import (check_history, metric_direction,
                               regression_verdict, summarize_verdicts)
from repro.obs.cost import (HOT_PROGRAMS, driver_step_cost,
                            hot_program_costs, pack_program_cost,
                            program_cost, serve_decode_cost)

__all__ = [
    "Histogram", "Telemetry",
    "hist_init", "hist_add", "hist_quantile", "hist_to_host",
    "telemetry_init", "telemetry_update", "telemetry_host",
    "telemetry_summary", "rollout_telemetry",
    "CompileTracker",
    "phase", "pull", "span", "trace_capture",
    "RunLog", "json_safe", "read_events", "run_manifest",
    "HistoryStore", "default_store", "history_manifest",
    "check_history", "metric_direction", "regression_verdict",
    "summarize_verdicts",
    "HOT_PROGRAMS", "program_cost", "driver_step_cost",
    "pack_program_cost", "serve_decode_cost", "hot_program_costs",
]
