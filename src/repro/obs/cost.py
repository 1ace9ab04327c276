"""Static cost/memory attribution for the repo's hot compiled programs.

XLA already knows what every compiled program costs — ``lowered
.compile().cost_analysis()`` reports the optimized HLO's FLOPs and bytes
accessed, ``memory_analysis()`` the argument/output/temp buffer sizes —
but nothing in the repo surfaced it. ``program_cost`` packages both into
one JSON-safe dict (FLOPs, bytes, arithmetic intensity = FLOPs/byte,
buffer sizes), and the three ``*_cost`` builders lower the hot programs
the ROADMAP's kernel work (Pallas backwards, bf16/int8 actor variants)
will be judged against:

* ``driver_step_cost``  — the ``RolloutDriver`` slot body (the
  ``lax.scan`` step: sample -> actor -> env step -> cond-train);
* ``pack_program_cost`` — a whole ``PackProgram`` episode (the vmapped,
  scan-fused sweep mega-batch);
* ``serve_decode_cost`` — one serve decode step (``make_serve_step`` at
  the final exit).

These are *static* analyses: no timing, no device execution beyond
compilation, deterministic per (code revision, backend, shape) — which
is exactly what makes them good history records: a kernel rewrite that
changes FLOPs or arithmetic intensity shows up as a step change in the
trend, noise-free. ``benchmarks/cost_attribution.py`` reports them into
``results/history/`` alongside the wall-clock rows.

A field the backend's analysis does not report comes back as ``None``;
a failing analysis raises.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

# The three standard hot programs, in reporting order.
HOT_PROGRAMS = ("driver_step", "sweep_pack", "serve_decode")


def program_cost(fn, *args, **kwargs) -> dict:
    """Lower+compile ``fn`` on the given arguments and report its cost.

    ``fn`` may be a ``jax.jit`` wrapper (its compile cache is reused and
    warmed — lowering the same shapes later is free) or a plain callable
    (jitted here). Returns a JSON-safe dict::

        {"flops": ..., "bytes_accessed": ..., "arithmetic_intensity": ...,
         "argument_bytes": ..., "output_bytes": ..., "temp_bytes": ...,
         "generated_code_bytes": ...}

    with ``None`` for a FLOPs or bytes figure the backend's cost analysis
    does not report.
    """
    if not hasattr(fn, "lower"):
        fn = jax.jit(fn)
    compiled = fn.lower(*args, **kwargs).compile()
    out = {"flops": None, "bytes_accessed": None,
           "arithmetic_intensity": None, "argument_bytes": None,
           "output_bytes": None, "temp_bytes": None,
           "generated_code_bytes": None}
    ca = compiled.cost_analysis()
    flops = ca.get("flops")
    nbytes = ca.get("bytes accessed")
    if flops is not None:
        out["flops"] = float(flops)
    if nbytes is not None:
        out["bytes_accessed"] = float(nbytes)
    if flops and nbytes:
        out["arithmetic_intensity"] = round(float(flops) / float(nbytes), 4)
    mem = compiled.memory_analysis()
    for field, key in (("argument_size_in_bytes", "argument_bytes"),
                       ("output_size_in_bytes", "output_bytes"),
                       ("temp_size_in_bytes", "temp_bytes"),
                       ("generated_code_size_in_bytes",
                        "generated_code_bytes")):
        out[key] = int(getattr(mem, field))
    return out


# --------------------------------------------------------- program builders
def driver_step_cost(*, n_devices: int = 6, n_servers: int = 2,
                     n_fleets: int = 2, method: str = "grle",
                     use_pallas: Optional[bool] = None) -> dict:
    """Cost of one ``RolloutDriver`` slot body (the scan step program)."""
    from repro.core.policy import agent_def
    from repro.mec.env import MECEnv
    from repro.mec.scenarios import make_scenario
    from repro.rollout.driver import RolloutDriver

    env = MECEnv(make_scenario("fig5_baseline", n_devices=n_devices))
    adef = agent_def(method, env, buffer_size=32, batch_size=8,
                     train_every=5, use_pallas=use_pallas)
    drv = RolloutDriver(adef, n_fleets=n_fleets)
    carry = drv.init_carry(jax.random.PRNGKey(0))
    cost = program_cost(drv._jit_slot, carry, None)
    cost["derived"] = (f"slot body: {method} M={n_devices} N={n_servers} "
                       f"B={n_fleets} fleets, train gated")
    return cost


def pack_program_cost(*, n_devices: int = 6, n_slots: int = 20,
                      seeds: int = 2,
                      use_pallas: Optional[bool] = None) -> dict:
    """Cost of one compiled ``PackProgram`` episode (gcn-family pack)."""
    from repro.sweep import SweepSpec, pack_cells
    from repro.sweep.runner import PackProgram

    spec = SweepSpec.from_names("fig5_baseline", "grle,grl", seeds,
                                n_devices=n_devices, n_slots=n_slots,
                                replay_capacity=16, batch_size=4,
                                train_every=5)
    (pack,) = pack_cells(spec.expand())
    prog = PackProgram(pack, use_pallas=use_pallas)
    cost = program_cost(prog._episode, prog._carries, prog._sps)
    cost["derived"] = (f"pack episode: {len(pack.cells)} cells "
                       f"(grle,grl x {seeds} seeds) M={n_devices} "
                       f"T={n_slots}")
    return cost


def serve_decode_cost(*, arch: str = "qwen1_5_0_5b", batch: int = 2,
                      cache_len: int = 64) -> dict:
    """Cost of one serve decode step (final exit, reduced config)."""
    from repro.configs import get_arch
    from repro.models.lm import model_for
    from repro.train.steps import make_serve_step

    cfg = get_arch(arch, reduced=True)
    model = model_for(cfg)
    params = model.init(jax.random.PRNGKey(0), cfg)
    cache = model.init_cache(cfg, batch, cache_len)
    step = jax.jit(make_serve_step(cfg, exit_layer=cfg.exit_layers[-1]))
    tokens = jnp.zeros((batch,), jnp.int32)
    pos = jnp.zeros((batch,), jnp.int32)
    cost = program_cost(step, params, cache, tokens, pos)
    cost["derived"] = (f"decode step: {arch} (reduced) b={batch} "
                       f"cache={cache_len} exit={cfg.exit_layers[-1]}")
    return cost


def hot_program_costs(quick: bool = True) -> dict:
    """The three standard programs' costs, keyed by ``HOT_PROGRAMS`` name.

    ``quick=False`` uses paper-scale shapes for the MEC programs (M=14,
    T=100) — the numbers that pair with the committed BENCH rows.
    """
    if quick:
        return {
            "driver_step": driver_step_cost(),
            "sweep_pack": pack_program_cost(),
            "serve_decode": serve_decode_cost(),
        }
    return {
        "driver_step": driver_step_cost(n_devices=14, n_fleets=4),
        "sweep_pack": pack_program_cost(n_devices=14, n_slots=100,
                                        seeds=4),
        "serve_decode": serve_decode_cost(batch=4, cache_len=256),
    }
