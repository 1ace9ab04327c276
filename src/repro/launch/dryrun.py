"""Multi-pod dry-run: lower + compile every (arch × input shape × mesh).

For each combination this proves the distribution config is coherent on
the production mesh (16×16 single pod / 2×16×16 multi-pod) and extracts
the roofline inputs:

  * cost_analysis  -> per-device HLO FLOPs & bytes accessed,
  * memory_analysis -> per-device buffer sizes (fits-in-HBM check),
  * HLO text       -> per-collective wire bytes (all-gather / all-reduce /
                      reduce-scatter / all-to-all / collective-permute).

Results append to results/dryrun.jsonl (resumable sweep). Usage:

  python -m repro.launch.dryrun --one <arch> <shape> <mesh>
  python -m repro.launch.dryrun --sweep [--mesh single|multi|both] [--fresh]
"""
from __future__ import annotations

import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
# The dry-run fakes its mesh on host devices: it and every child it
# starts stay off the accelerator, which another process may hold.
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST precede any jax import/device query (device count locks on init).

import argparse
import json
import re
import subprocess
import sys
import time

_SHAPE_RE = re.compile(r"(pred|s4|s8|s16|s32|u8|u16|u32|u64|bf16|f16|f32|f64|"
                       r"c64|c128)\[([0-9,]*)\]")
_DTYPE_BYTES = {"pred": 1, "s4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
                "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4,
                "u64": 8, "f64": 8, "c64": 8, "c128": 16}
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
# wire-traffic factor per output byte (ring algorithms, large-n limit)
_WIRE_FACTOR = {"all-gather": 1.0, "all-reduce": 2.0, "reduce-scatter": 1.0,
                "all-to-all": 1.0, "collective-permute": 1.0}


def _shape_bytes(text: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(text):
        dims = m.group(2)
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[m.group(1)]
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Sum result bytes per collective op kind (start ops only, not -done)."""
    out = {k: {"bytes": 0, "count": 0, "wire_bytes": 0.0} for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.strip()
        if "=" not in ls:
            continue
        lhs, _, rhs = ls.partition("=")
        for kind in _COLLECTIVES:
            tok = f" {kind}(" if not rhs.strip().startswith(kind) else None
            if rhs.strip().startswith(kind + "(") or (tok and tok in rhs):
                # result type is on the lhs of '=' in post-opt HLO dumps;
                # fall back to first shape group on the rhs when absent.
                nbytes = _shape_bytes(lhs) or _shape_bytes(rhs.split(")")[0])
                out[kind]["bytes"] += nbytes
                out[kind]["count"] += 1
                out[kind]["wire_bytes"] += nbytes * _WIRE_FACTOR[kind]
                break
    return {k: v for k, v in out.items() if v["count"]}


def run_one(arch: str, shape_name: str, mesh_kind: str) -> dict:
    import jax
    from repro.configs import get_arch
    from repro.launch.mesh import make_production_mesh
    from repro.launch import specs as S
    from repro.models.config import INPUT_SHAPES
    from repro.train.steps import make_prefill_step, make_serve_step, \
        make_train_step

    # covers a jax imported before this module set JAX_PLATFORMS
    jax.config.update("jax_platforms", "cpu")
    t0 = time.time()
    cfg = S.arch_for_shape(get_arch(arch), INPUT_SHAPES[shape_name])
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    from repro.sharding import runtime as R
    if R.enabled("seq_parallel") and shape.mode in ("train", "prefill") \
            and shape.seq_len % mesh.shape["model"] == 0:
        R.set_activation_spec(R.default_seq_parallel_spec(mesh))
    if R.enabled("no_remat"):
        import dataclasses as _dc
        cfg = _dc.replace(cfg, remat=False)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "devices": int(len(mesh.devices.flat)),
           "opts": sorted(R.opts())}

    with mesh:
        if shape.mode == "train":
            state, sspecs, opt = S.train_state_struct(cfg, mesh)
            batch = S.batch_struct(cfg, shape, mesh)
            fn = make_train_step(cfg, opt)
            jitted = jax.jit(fn, donate_argnums=(0,))
            args = (state, batch)
        elif shape.mode == "prefill":
            params, _ = S.params_struct(cfg, mesh)
            batch = S.batch_struct(cfg, shape, mesh)
            fn = make_prefill_step(cfg)
            jitted = jax.jit(fn)
            args = (params, batch)
        else:  # decode
            params, _ = S.params_struct(cfg, mesh)
            cache, tokens, pos = S.decode_struct(cfg, shape, mesh)
            fn = make_serve_step(cfg)
            jitted = jax.jit(fn, donate_argnums=(1,))
            args = (params, cache, tokens, pos)

        lowered = jitted.lower(*args)
        rec["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)

        ca = compiled.cost_analysis()
        rec["flops"] = float(ca.get("flops", -1.0))
        rec["bytes_accessed"] = float(ca.get("bytes accessed", -1.0))
        try:
            ma = compiled.memory_analysis()
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes",
                      "alias_size_in_bytes"):
                v = getattr(ma, k, None)
                if v is not None:
                    rec[k] = int(v)
        except Exception as e:  # noqa: BLE001
            rec["memory_analysis_error"] = str(e)
        txt = compiled.as_text()
        rec["collectives_flat"] = parse_collectives(txt)
        from repro.launch.analysis import collective_bytes_nested
        rec["collectives"] = collective_bytes_nested(txt)
        rec["hlo_chars"] = len(txt)
    rec["ok"] = True
    rec["total_s"] = round(time.time() - t0, 2)
    return rec


ALL_MESHES = ("single", "multi")


def combos(meshes):
    from repro.configs import ARCH_IDS
    from repro.models.config import INPUT_SHAPES
    for arch in ARCH_IDS:
        for shape in INPUT_SHAPES:
            for mesh in meshes:
                yield arch, shape, mesh


def sweep(out_path: str, meshes, timeout: int, fresh: bool) -> int:
    done = set()
    if not fresh and os.path.exists(out_path):
        with open(out_path) as f:
            for line in f:
                try:
                    r = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if r.get("ok"):
                    done.add((r["arch"], r["shape"], r["mesh"]))
    todo = [c for c in combos(meshes) if c not in done]
    print(f"[dryrun] {len(done)} done, {len(todo)} to go", flush=True)
    failures = 0
    for arch, shape, mesh in todo:
        print(f"[dryrun] {arch} × {shape} × {mesh} ...", flush=True)
        cmd = [sys.executable, "-m", "repro.launch.dryrun", "--one",
               arch, shape, mesh, "--out", out_path]
        try:
            p = subprocess.run(cmd, timeout=timeout, capture_output=True,
                               text=True)
            if p.returncode != 0:
                failures += 1
                err = (p.stderr or "")[-2000:]
                with open(out_path, "a") as f:
                    f.write(json.dumps({
                        "arch": arch, "shape": shape, "mesh": mesh,
                        "ok": False, "error": err}) + "\n")
                print(f"[dryrun]   FAILED: {err.splitlines()[-1] if err else '?'}",
                      flush=True)
            else:
                print(f"[dryrun]   ok {p.stdout.strip()[-120:]}", flush=True)
        except subprocess.TimeoutExpired:
            failures += 1
            with open(out_path, "a") as f:
                f.write(json.dumps({"arch": arch, "shape": shape,
                                    "mesh": mesh, "ok": False,
                                    "error": f"timeout {timeout}s"}) + "\n")
            print("[dryrun]   TIMEOUT", flush=True)
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--one", nargs=3, metavar=("ARCH", "SHAPE", "MESH"))
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun.jsonl")
    ap.add_argument("--timeout", type=int, default=2400)
    ap.add_argument("--fresh", action="store_true")
    args = ap.parse_args()
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)

    if args.one:
        arch, shape, mesh = args.one
        rec = run_one(arch, shape, mesh)
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")
        print(json.dumps({k: rec[k] for k in
                          ("arch", "shape", "mesh", "flops", "compile_s")
                          if k in rec}))
        return
    meshes = ALL_MESHES if args.mesh == "both" else (args.mesh,)
    failures = sweep(args.out, meshes, args.timeout, args.fresh)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
