"""BENCHMARK.json against the benchmark's contract, and every file a cell
needs found by name.

    python -m pytest benchmarks/chip/tests
"""
import json
import re
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import chiplib  # noqa: E402

SPEC = chiplib.benchmark_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(SPEC["command"]) <= 32
    assert all(PATH.match(p) and ".." not in p for p in SPEC["paths"])
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len(json.dumps(SPEC)) <= 64 * 1024


def test_command_stays_inside_paths():
    for word in SPEC["command"]:
        assert not word.startswith("/") and ".." not in word
        if "/" in word:
            assert any(word.startswith(p + "/") for p in SPEC["paths"])


def test_names_units_and_text():
    names = ([c["name"] for c in SPEC["configs"]]
             + [w["name"] for w in SPEC["workloads"]]
             + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
             + [w["traffic"] for w in SPEC["workloads"]]
             + [k for c in SPEC["configs"] for k in c["reduced"]])
    assert all(NAME.match(n) for n in names), names
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = [e["name"] for e in SPEC[group]]
        assert len(seen) == len(set(seen)), group
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for text in ([c["source"] for c in SPEC["configs"]]
                 + [e["why"] for e in SPEC["configs"] + SPEC["workloads"]]
                 + [m["layer"] for m in SPEC["per_layer"]]
                 + SPEC["command"]):
        assert 1 <= len(text) <= 200 and "\n" not in text \
            and "\t" not in text


def test_entry_keys():
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                           "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                           "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def test_bounds_and_setup():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    cells = [w["name"] for w in SPEC["workloads"]]
    for cell in cells:
        reported = [m["name"] for m in chiplib.cell_metrics(SPEC, cell,
                                                            False)]
        assert "setup_s" in reported and len(reported) >= 2, cell
        assert chiplib.cell_metrics(SPEC, cell, True), cell
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert cell in e2e[m["moves"]].get("workloads", cells), \
                (m["name"], cell)


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_cell_files_found_by_name(cell):
    w = chiplib.find_cell(SPEC, cell)
    cfg = chiplib.config_file(SPEC, w["config"])
    mix = chiplib.traffic_file(w["traffic"])
    assert (BENCH / "drivers" / f"{mix['driver']}.py").exists()
    for m in chiplib.cell_metrics(SPEC, cell, True):
        assert callable(chiplib.metric_reader(m["name"]))
    assert chiplib.limits_file(cell), f"no limits for {cell}"
    assert cfg["name"] == w["config"]
    assert callable(chiplib.arch(cfg).program_config)
    assert callable(chiplib.reference(cfg).served_gaps)


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda c: c["name"])
def test_config_files(entry):
    path = Path(chiplib.ROOT / entry["file"])
    assert any(entry["file"].startswith(p + "/") for p in SPEC["paths"])
    cfg = json.loads(path.read_text())
    assert cfg["name"] == entry["name"] and cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"]
    widths = re.compile(r"(_dim$|_rank$|^(hidden|intermediate|latent|state|"
                        r"projection|head)_size$|experts_per_tok|expand)")
    assert not any(widths.search(k) for k in entry["reduced"])
    files = [c["file"] for c in SPEC["configs"]]
    assert len(files) == len(set(files))


def test_four_chip_share():
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)
