"""The correctness check's two readings for a cell, on the chip.

    python3 -m bench.control --workload <cell> --seeds 1,2,3 --seconds 5

For each seed it runs the cell as ``bench.run`` does, with a short
window at the cell's own load, and prints one JSON line: the program's
readings (what a run compares with its limits) and the control's. For a
serving cell that is ``logit_gap``, the control the plain reference with
fp8 weights read at the same positions of the same requests. A driver
with a ``control_readings`` of its own (training) reads every number of
its check on the control run in the program's place. The program's
readings over a dozen seeds or more set the lower end of each limit, the
control's the upper end.
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def control_gap(ref, cfg, checked) -> float:
    """The control's ``logit_gap`` over the rows a run checked."""
    from bench.drivers.serve import check_gaps
    w = checked["weights"]
    return check_gaps(lambda t, e, s: ref.control_gaps(cfg, w, t, e), cfg,
                      checked["rows"], checked["length"])


def main(argv=None, *, require_tpu: bool = True, resolved=None) -> list:
    from bench import manifest, run
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    cell = resolved or manifest.resolve(args.workload)
    sys.path.insert(0, str(run.ROOT / "src"))
    if require_tpu:
        jax = run.setup_jax()
    else:
        import jax
    devs = run.devices(jax, cell["cell"]["chips"], require_tpu)
    out = []
    driver = cell["driver"]
    for seed in [int(s) for s in args.seeds.split(",")]:
        ctx = dict(config=cell["config"], traffic=cell["traffic"],
                   reference=cell["reference"], seed=seed,
                   seconds=args.seconds, trace=False, trace_dir=None,
                   t_process=time.time(), peaks=None)
        with jax.default_device(devs[0]):
            res = driver.run(ctx)
            if hasattr(driver, "control_readings"):
                row = {"program": {k: v for k, (v, _) in
                                   res["checks"].items()},
                       "control": driver.control_readings(ctx),
                       "limits": {k: lim for k, (_, lim) in
                                  res["checks"].items()}}
            else:
                row = {"program": res["checks"]["logit_gap"][0],
                       "control": control_gap(cell["reference"],
                                              cell["config"], res["checked"]),
                       "limit": res["checks"]["logit_gap"][1],
                       "checked_tokens": res["checked_tokens"]}
        row = {"workload": args.workload, "seed": seed, **row,
               "attempted": res["attempted"]}
        print(json.dumps(row), flush=True)
        out.append(row)
    return out


if __name__ == "__main__":
    main()
