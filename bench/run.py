"""Run one benchmark cell on the chip and print its result line.

    python3 -m bench.run --workload <config>.<traffic> --seed <n> \
        --seconds <s> --trace <0|1>

Standard output ends with one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace
0``, its per-layer metrics with ``--trace 1``), ``device``, with
``--trace 1`` a ``breakdown``, and last ``checks``: each compared number
beside its limit. The line before it reports the compiles and traces
inside the measured window. Without an accelerator, or with fewer chips
than the cell asks for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


def process_start() -> float:
    """Wall-clock time at which this process started (Linux), else now."""
    try:
        with open("/proc/self/stat") as f:
            ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - uptime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.time()


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def setup_jax():
    """Compile cache: ``JAX_COMPILATION_CACHE_DIR`` if set, else the fixed
    ``<checkout>/.jax_cache``; every program is cached."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def devices(jax, chips: int, require_tpu: bool = True):
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise SystemExit(f"no accelerator: JAX's default device is "
                         f"{devs[0].platform} ({devs[0].device_kind})")
    if len(devs) < chips:
        raise SystemExit(f"the cell asks for {chips} chips, "
                         f"JAX sees {len(devs)}")
    return devs[:chips]


def run_cell(args, *, t_process: float, require_tpu: bool = True,
             resolved: dict | None = None):
    """Drive one cell; returns (result line, the driver's whole output).

    ``require_tpu=False`` and ``resolved`` (a cell as ``manifest.resolve``
    returns it, possibly shrunk) let a test drive a run on the CPU; the
    compile cache is then left as the caller set it.
    """
    from bench import manifest, peaks
    cell = resolved or manifest.resolve(args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    if require_tpu:
        jax = setup_jax()
    else:
        import jax
    devs = devices(jax, cell["cell"]["chips"], require_tpu)
    kind = devs[0].device_kind
    chip = peaks.chip_peaks(kind) if require_tpu else None
    with jax.default_device(devs[0]):
        out = cell["driver"].run(dict(
            config=cell["config"], traffic=cell["traffic"],
            reference=cell["reference"], seed=args.seed,
            seconds=args.seconds, trace=bool(args.trace),
            trace_dir=str(TRACE_DIR / args.workload), t_process=t_process,
            peaks=chip))
    units = {m["name"]: m["unit"] for m in cell["end_to_end"]
             + cell["per_layer"]}
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": out["memory_peak_bytes"]}
    line = {"correct": bool(out["correct"]), "attempted": out["attempted"],
            "failed": out["failed"]}
    if args.trace:
        from bench import trace as tr
        lc = out["layer_ctx"]
        values = {name: reader.read(lc)
                  for name, reader in cell["readers"].items()}
        line["metrics"] = {k: {"value": v, "unit": units[k]}
                           for k, v in values.items() if v is not None}
        ev = lc["events"]
        window = tr.span(ev, "bench/window")
        device["busy_s"] = tr.busy_seconds(ev, *window)
        device["window_s"] = (window[1] - window[0]) * 1e-9
        line["device"] = device
        line["breakdown"] = {"device_ops": tr.top_ops(ev, *window),
                             "idle_gaps": tr.idle_gaps(ev, *window)}
    else:
        line["metrics"] = {m["name"]: {"value": out["metrics"][m["name"]],
                                       "unit": m["unit"]}
                           for m in cell["end_to_end"]}
        line["device"] = device
    line["checks"] = out["checks"]
    return line, out


def main(argv=None) -> int:
    t_process = process_start()
    args = parse(argv)
    line, out = run_cell(args, t_process=t_process)
    print(json.dumps({"compiles_in_window": out["compiles_in_window"],
                      "setup_compiles": out["setup_compiles"],
                      "checked_tokens": out["checked_tokens"]}), flush=True)
    for name, (value, limit) in line["checks"].items():
        print(f"[bench] check {name}: {value!r} (limit {limit!r})",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
