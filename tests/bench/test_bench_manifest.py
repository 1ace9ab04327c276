"""BENCHMARK.json keeps to its contract, and every cell resolves by name."""
import json
import re

import pytest

from bench import manifest

M = manifest.load_manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
CELLS = [w["name"] for w in M["workloads"]]


def test_top_level_keys_and_size():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert manifest.MANIFEST.stat().st_size <= 64 * 1024
    assert 1 <= M["run_seconds"] <= 51 and isinstance(M["run_seconds"], int)
    assert 1 <= len(M["paths"]) <= 16
    for p in M["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (manifest.ROOT / p).is_dir()
    assert 1 <= len(M["command"]) <= 32


def test_names_and_units_use_allowed_characters():
    names = []
    for c in M["configs"]:
        names.append(c["name"])
        assert len(c["reduced"]) <= 16
        names += c["reduced"]
    for w in M["workloads"]:
        names += [w["name"], w["config"], w["traffic"]]
        assert w["chips"] in (1, 4)
    for m in M["end_to_end"] + M["per_layer"]:
        names.append(m["name"])
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for n in names:
        assert NAME.match(n), n
    for text in ([c["why"] for c in M["configs"]]
                 + [w["why"] for w in M["workloads"]]
                 + [m["layer"] for m in M["per_layer"]]
                 + [c["source"] for c in M["configs"]] + M["command"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_metrics_and_bounds():
    e2e = {m["name"]: m for m in M["end_to_end"]}
    assert "setup_s" in e2e
    for m in M["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in M["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    all_names = [x["name"] for x in M["end_to_end"] + M["per_layer"]]
    assert len(all_names) == len(set(all_names))


def test_four_chip_cells_within_share():
    four = sum(1 for w in M["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(M["workloads"]) // 2)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_every_piece_by_name(cell):
    r = manifest.resolve(cell)
    assert r["config"]["name"] == r["cell"]["config"]
    assert callable(r["driver"].run)
    assert callable(r["reference"].make_weights)
    e2e = {m["name"] for m in r["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert r["per_layer"], "every cell reports a per-layer metric"
    for name, reader in r["readers"].items():
        assert callable(reader.read), name
    assert 1 <= r["traffic"]["clients"] <= r["config"]["batch_slots"]
    lay = r["driver"].layout(r["config"])   # found by the config's name
    assert callable(lay.arch_config) and callable(lay.program_params)


@pytest.mark.parametrize("cell", CELLS)
def test_per_layer_workloads_report_what_they_move(cell):
    for m in M["per_layer"]:
        for w in m.get("workloads", []):
            assert w in CELLS
            e2e = [e for e in M["end_to_end"] if e["name"] == m["moves"]]
            assert w in e2e[0].get("workloads", CELLS)


def test_config_files_are_distinct_and_under_paths():
    files = [c["file"] for c in M["configs"]]
    assert len(files) == len(set(files))
    for f in files:
        assert any(f.startswith(p + "/") for p in M["paths"])
        json.loads((manifest.ROOT / f).read_text())


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        manifest.resolve("no_such.cell")
