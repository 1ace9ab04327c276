"""Benchmark runner — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME,...]

| module              | paper artifact                     |
|---------------------|------------------------------------|
| exit_profile        | Table I / Fig 3                    |
| convergence         | Fig 4                              |
| vary_devices        | Fig 5                              |
| vary_capacity       | Fig 6                              |
| vary_inference_time | Fig 7                              |
| imperfect_csi       | Fig 8                              |
| kernels             | kernel microbench (us_per_call)    |
| roofline            | deliverable (g), from the dry-run  |
| rollout_throughput  | scan-fused vs per-slot loop        |
| sweep_throughput    | packed sweep vs per-cell loop      |
| pop_throughput      | vmapped population vs member loop  |
| cost_attribution    | FLOPs/bytes of the hot programs    |

Every saved row is stamped (backend, jax device count, git rev) and
appended to the run-history store (``results/history/``) for cross-run
trend/regression tracking (``python -m repro.launch history``,
``tools/check_perf_regression.py``). ``--only`` with an unknown module
name is an error, not a silent skip.
"""
from __future__ import annotations

import argparse
import time


def bench_kernels(quick: bool = False):
    """us_per_call of the kernel reference paths (jnp, CPU) — the CSV the
    scaffold asks for; TPU wall-time belongs to real hardware."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ref

    key = jax.random.PRNGKey(0)
    rows = []

    from benchmarks.common import timed

    def timeit(name, fn, *args, derived=""):
        fn(*args)  # compile/warm
        n = 5 if quick else 20
        wall = sum(timed(fn, *args)[1] for _ in range(n))
        us = wall / n * 1e6
        rows.append({"name": name, "us_per_call": round(us, 1),
                     "derived": derived})

    ks = jax.random.split(key, 6)
    q = jax.random.normal(ks[0], (2, 512, 8, 64))
    k = jax.random.normal(ks[1], (2, 512, 2, 64))
    v = jax.random.normal(ks[2], (2, 512, 2, 64))
    timeit("flash_attention_ref_512", jax.jit(ref.flash_attention_ref),
           q, k, v, derived="b2 s512 h8 kv2 d64")
    qd = jax.random.normal(ks[0], (4, 8, 64))
    kd = jax.random.normal(ks[1], (4, 4096, 2, 64))
    vd = jax.random.normal(ks[2], (4, 4096, 2, 64))
    lens = jnp.full((4,), 4096, jnp.int32)
    timeit("decode_attention_ref_4k", jax.jit(ref.decode_attention_ref),
           qd, kd, vd, lens, derived="b4 s4096")
    qs = jax.random.normal(ks[0], (2, 256, 4, 32))
    ks_ = jax.random.normal(ks[1], (2, 256, 4, 32))
    vs = jax.random.normal(ks[2], (2, 256, 4, 32))
    w = -jnp.exp(jax.random.normal(ks[3], (2, 256, 4, 32)) * 0.5)
    from repro.models.ssm import chunked_linear_attn
    timeit("ssm_chunked_256", jax.jit(
        lambda *a: chunked_linear_attn(*a, chunk=64)[0]), qs, ks_, vs, w,
        derived="b2 t256 h4 dk32")
    adj = jax.random.uniform(ks[4], (64, 14, 10))
    hs = jax.random.normal(ks[5], (64, 14, 6))
    hn = jax.random.normal(ks[0], (64, 10, 4))
    ws = jax.random.normal(ks[1], (6, 128))
    wn = jax.random.normal(ks[2], (4, 128))
    b = jnp.zeros((128,))
    timeit("gcn_agg_ref_minibatch64", jax.jit(ref.gcn_agg_ref),
           adj, hs, hn, ws, wn, b, derived="paper GCN layer-1, batch 64")
    from benchmarks.common import save_rows
    save_rows("kernels", rows)
    for r in rows:
        print(f"  {r['name']:28s} {r['us_per_call']:>10.1f} us  {r['derived']}")
    return rows


BENCHES = ("exit_profile", "convergence", "vary_devices", "vary_capacity",
           "vary_inference_time", "imperfect_csi", "kernels", "roofline",
           "rollout_throughput", "sweep_throughput", "pop_throughput",
           "cost_attribution")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default="",
                    help=f"comma-separated subset of: {', '.join(BENCHES)}")
    args = ap.parse_args(argv)
    only = set(args.only.split(",")) if args.only else set(BENCHES)
    unknown = sorted(only - set(BENCHES))
    if unknown:
        import difflib
        hints = []
        for name in unknown:
            close = difflib.get_close_matches(name, BENCHES, n=2)
            hints.append(name + (f" (did you mean {' or '.join(close)}?)"
                                 if close else ""))
        ap.error(f"unknown benchmark module(s): {'; '.join(hints)} "
                 f"(choose from {', '.join(BENCHES)})")

    print("name,us_per_call,derived")
    all_rows = {}
    for name in BENCHES:
        if name not in only:
            continue
        t0 = time.perf_counter()
        print(f"=== {name} ===", flush=True)
        if name == "kernels":
            rows = bench_kernels(args.quick)
        else:
            import importlib
            mod = importlib.import_module(f"benchmarks.{name}")
            rows = mod.run(quick=args.quick)
        all_rows[name] = rows
        print(f"=== {name} done in {time.perf_counter() - t0:.0f}s ===",
              flush=True)

    # final CSV digest (name,us_per_call,derived convention)
    print("\n# digest")
    print("name,us_per_call,derived")
    for name, rows in all_rows.items():
        for r in rows or []:
            if "us_per_call" in r:
                print(f"{r['name']},{r['us_per_call']},{r['derived']}")
            elif "cells_per_s" in r:
                print(f"{r['name']},,cells_per_s={r['cells_per_s']};"
                      f"{r['derived']}")
            elif "slots_per_s" in r:
                extra = (f";vs_sequential="
                         f"{r['vs_sequential_speedup']}x"
                         if "vs_sequential_speedup" in r else "")
                print(f"{r['name']},,slots_per_s={r['slots_per_s']}"
                      f"{extra}")
            elif "margin" in r:
                print(f"{r['name']},,margin={r['margin']:+.4f};"
                      f"curriculum_wins={r['curriculum_wins']}")
            elif "avg_accuracy" in r:
                label = (f"{name}/{r['method']}-M{r['n_devices']}"
                         f"-t{int(r['slot_ms'])}")
                print(f"{label},,acc={r['avg_accuracy']:.3f};"
                      f"ssp={r['ssp']:.3f};thr={r['throughput_tps']:.1f}")
            elif "exit" in r:
                print(f"{name}/exit{r['exit']},,acc={r['accuracy']:.3f};"
                      f"paper_acc={r.get('paper_accuracy', '')}")
            elif "final_moving_Qhat" in r:
                print(f"{name}/{r['method']},,Qhat="
                      f"{r['final_moving_Qhat']:.3f}")
            elif "final_moving_reward" in r:
                print(f"{name}/{r['method']},,reward="
                      f"{r['final_moving_reward']:.3f}")
            elif "dominant" in r:
                print(f"{name}/{r['arch']}-{r['shape']},,dom={r['dominant']};"
                      f"useful={r['useful_fraction']:.2f}")
            elif "flops" in r:
                print(f"{r['name']},,flops={r['flops']:.3e};"
                      f"bytes={r.get('bytes_accessed', 0):.3e};"
                      f"ai={r.get('arithmetic_intensity', '')}")


if __name__ == "__main__":
    from repro.obs.compile import use_compile_cache
    use_compile_cache()
    main()
