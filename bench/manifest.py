"""Find every piece of a cell by its name.

Nothing here lists configurations, traffic mixes, drivers or metrics:
a new one is a new file plus its entry in ``BENCHMARK.json``.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
MANIFEST = ROOT / "BENCHMARK.json"


def load_manifest(path: Path = MANIFEST) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import one file by path (metric files carry dots in their names)."""
    spec = importlib.util.spec_from_file_location(
        "bench_piece_" + path.stem.replace(".", "_").replace("-", "_"), path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(cell_name: str, manifest: dict | None = None) -> dict:
    """Everything one cell needs, found by name.

    Returns the manifest entries and loaded pieces: ``cell``, ``config``
    (the sizes file as a dict), ``reference`` (the module beside it),
    ``traffic`` (dict), ``driver`` (module), ``end_to_end`` and
    ``per_layer`` (the metric entries this cell reports) and
    ``readers`` (per-layer metric name -> module).
    """
    manifest = manifest or load_manifest()
    cells = {w["name"]: w for w in manifest["workloads"]}
    if cell_name not in cells:
        raise KeyError(f"unknown workload {cell_name!r} "
                       f"(have {sorted(cells)})")
    cell = cells[cell_name]
    configs = {c["name"]: c for c in manifest["configs"]}
    entry = configs[cell["config"]]
    config = load_json(ROOT / entry["file"])
    reference = load_module((ROOT / entry["file"]).with_suffix(".py"))
    traffic = load_json(BENCH_DIR / "traffic" / f"{cell['traffic']}.json")
    driver = load_module(BENCH_DIR / "drivers" / f"{config['driver']}.py")

    def reports(metric: dict) -> bool:
        return cell_name in metric.get("workloads", [cell_name])

    end_to_end = [m for m in manifest["end_to_end"] if reports(m)]
    e2e_names = {m["name"] for m in end_to_end}
    per_layer = [m for m in manifest["per_layer"]
                 if m["moves"] in e2e_names and reports(m)]
    readers = {m["name"]: load_module(BENCH_DIR / "metrics"
                                      / f"{m['name']}.py")
               for m in per_layer}
    return dict(cell=cell, config=config,
                reference=reference, traffic=traffic, driver=driver,
                end_to_end=end_to_end, per_layer=per_layer, readers=readers)
