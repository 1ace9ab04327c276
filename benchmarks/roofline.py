"""Roofline analysis — deliverable (g).

Per (arch × shape) on the single-pod mesh (256 chips):

    compute term    = FLOPs_global / (chips × peak_FLOP/s)
    memory term     = HBM_bytes_global / (chips × HBM_bw)
    collective term = wire_bytes_per_device / link_bw

Methodology (EXPERIMENTS.md §Roofline): the compute/memory numerators come
from the analytic per-op model in ``repro.launch.analysis`` because the CPU
backend's ``cost_analysis`` counts ``lax.scan`` bodies once (validated in
tests against scan-free configs). Collective bytes are parsed from the
SPMD-partitioned HLO of the actual compiled dry-run, with while-body ops
multiplied by their loop trip counts. ``useful_fraction`` =
MODEL_FLOPS (6·N·D train / 2·N_active·D inference) / analytic total — the
share of compiled compute that is "the model" rather than attention
quadratic terms, remat recompute, exits and dispatch.
"""
from __future__ import annotations

import json
import os

from benchmarks.common import RESULTS_DIR, save_rows
from repro.configs import get_arch
from repro.launch.analysis import flops_bytes_model
from repro.launch.specs import arch_for_shape
from repro.models.config import INPUT_SHAPES
from repro.obs.peaks import TPU_V5E, chip_peaks

CHIPS = 256
V5E = chip_peaks(TPU_V5E)  # the pod modelled here is v5e

_ADVICE = {
    "compute": ("compute-bound: raise MXU utilization — larger per-device "
                "batch, cheaper remat policy, fewer non-model FLOPs "
                "(attention span, duplicate exits)"),
    "memory": ("HBM-bound: cut bytes touched — fuse elementwise chains, "
               "bf16 activations, shard KV cache/optimizer further, raise "
               "arithmetic intensity with bigger tiles"),
    "collective": ("ICI-bound: reduce wire bytes — reduce-scatter instead "
                   "of all-reduce, overlap collectives with compute, "
                   "re-place shardings so the hot tensor stays local"),
}


def run(quick: bool = False, path: str | None = None):
    path = path or os.path.join(RESULTS_DIR, "dryrun.jsonl")
    recs = {}
    if os.path.exists(path):
        with open(path) as f:
            for line in f:
                try:
                    r = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if r.get("ok"):
                    recs[(r["arch"], r["shape"], r["mesh"])] = r
    rows = []
    for (arch, shape_name, mesh), r in sorted(recs.items()):
        if mesh != "single":
            continue
        shape = INPUT_SHAPES[shape_name]
        cfg = arch_for_shape(get_arch(arch), shape)
        m = flops_bytes_model(cfg, shape)
        t_comp = m["flops"] / (CHIPS * V5E.flops_bf16)
        t_mem = m["bytes"] / (CHIPS * V5E.hbm_bw)
        wire = sum(c["wire_bytes"] for c in r.get("collectives", {}).values())
        t_coll = wire / V5E.ici_bw
        terms = {"compute": t_comp, "memory": t_mem, "collective": t_coll}
        dominant = max(terms, key=terms.get)
        rows.append({
            "arch": arch, "shape": shape_name,
            "compute_s": t_comp, "memory_s": t_mem, "collective_s": t_coll,
            "dominant": dominant,
            "model_flops": m["model_flops"],
            "useful_fraction": m["model_flops"] / m["flops"],
            "hlo_flops_per_device": r.get("flops"),
            "collective_wire_bytes_per_device": wire,
            "advice": _ADVICE[dominant],
            "hbm_per_device_gb": r.get("temp_size_in_bytes", 0) / 1e9,
        })
    save_rows("roofline", rows)
    for row in rows:
        print(f"  {row['arch']:18s} {row['shape']:12s} "
              f"comp={row['compute_s'] * 1e3:9.2f}ms "
              f"mem={row['memory_s'] * 1e3:9.2f}ms "
              f"coll={row['collective_s'] * 1e3:9.2f}ms "
              f"dom={row['dominant']:10s} useful={row['useful_fraction']:.2f}"
              f" tmp={row['hbm_per_device_gb']:.1f}GB",
              flush=True)
    return rows


def to_markdown(rows) -> str:
    out = ["| arch | shape | compute (ms) | memory (ms) | collective (ms) | "
           "dominant | useful FLOP frac | temp HBM/dev (GB) |",
           "|---|---|---|---|---|---|---|---|"]
    for r in rows:
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_s'] * 1e3:.2f} | "
            f"{r['memory_s'] * 1e3:.2f} | {r['collective_s'] * 1e3:.2f} | "
            f"{r['dominant']} | {r['useful_fraction']:.2f} | "
            f"{r['hbm_per_device_gb']:.1f} |")
    return "\n".join(out)
