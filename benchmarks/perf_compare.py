"""§Perf comparison: baseline (results/dryrun.jsonl) vs optimized
(results/dryrun_opt.jsonl) roofline terms for the hillclimb pairs.

    PYTHONPATH=src python -m benchmarks.perf_compare
"""
from __future__ import annotations

import json
import os

from benchmarks.common import RESULTS_DIR
from repro.configs import get_arch
from repro.launch.analysis import flops_bytes_model
from repro.launch.specs import arch_for_shape
from repro.models.config import INPUT_SHAPES
from repro.obs.peaks import TPU_V5E, chip_peaks

CHIPS = 256
V5E = chip_peaks(TPU_V5E)  # the pod modelled here is v5e


def terms(rec):
    shape = INPUT_SHAPES[rec["shape"]]
    cfg = arch_for_shape(get_arch(rec["arch"]), shape)
    m = flops_bytes_model(cfg, shape)
    wire = sum(c["wire_bytes"] for c in rec.get("collectives", {}).values())
    return {
        "compute_s": m["flops"] / (CHIPS * V5E.flops_bf16),
        "memory_s": m["bytes"] / (CHIPS * V5E.hbm_bw),
        "collective_s": wire / V5E.ici_bw,
        "temp_gb": rec.get("temp_size_in_bytes", 0) / 1e9,
        "wire_gb": wire / 1e9,
        "opts": ",".join(rec.get("opts", [])) or "baseline",
    }


def load(path):
    recs = []
    p = os.path.join(RESULTS_DIR, path)
    if os.path.exists(p):
        for line in open(p):
            try:
                r = json.loads(line)
            except json.JSONDecodeError:
                continue
            if r.get("ok"):
                recs.append(r)
    return recs


def main() -> None:
    base = {(r["arch"], r["shape"], r["mesh"]): r
            for r in load("dryrun.jsonl")}
    opts = load("dryrun_opt.jsonl")
    print(f"{'pair':42s} {'variant':28s} {'comp_s':>8s} {'mem_s':>8s} "
          f"{'coll_s':>9s} {'temp_GB':>8s}")
    seen = set()
    for r in opts:
        key = (r["arch"], r["shape"], r["mesh"])
        if key in base and key not in seen:
            seen.add(key)
            t = terms(base[key])
            print(f"{r['arch']+'×'+r['shape']:42s} {'baseline':28s} "
                  f"{t['compute_s']:8.2f} {t['memory_s']:8.3f} "
                  f"{t['collective_s']:9.2f} {t['temp_gb']:8.1f}")
        t = terms(r)
        print(f"{'':42s} {t['opts']:28s} "
              f"{t['compute_s']:8.2f} {t['memory_s']:8.3f} "
              f"{t['collective_s']:9.2f} {t['temp_gb']:8.1f}")


if __name__ == "__main__":
    main()
