"""BENCHMARK.json against the rules it is checked by, and every file of
every cell found by name."""
import json
import os
import re

import pytest

from harness import spec
from harness.driver import driver_class

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


@pytest.fixture(scope="module")
def bench():
    path = os.path.join(spec.ROOT, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    return spec.load_benchmark()


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level(bench):
    assert set(bench) == KEYS
    assert 1 <= len(bench["paths"]) <= 16
    for p in bench["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
    cmd = bench["command"]
    assert 1 <= len(cmd) <= 32 and all(_line(w) for w in cmd)
    for w in cmd:
        if "/" in w:
            assert not w.startswith("/") and ".." not in w
            assert any(w.startswith(p.rstrip("/") + "/")
                       for p in bench["paths"])
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51


def test_full_check_fits_its_time(bench):
    """2 + 14 x 24 runs of run_seconds + 60 s, 2 x 90 s of compile per
    cell and 1200 s spare, inside 43200 s."""
    cells = 24
    total = ((2 + 14 * cells) * (bench["run_seconds"] + 60)
             + cells * 2 * 90 + 1200)
    assert total <= 43200


def test_names_units_and_keys(bench):
    seen = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert _line(m["layer"])
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in bench[group]]
        assert len(names) == len(set(names))
        seen |= set(names)
    assert len(bench["configs"]) <= 24 and len(bench["workloads"]) <= 24
    assert len({(w["config"], w["traffic"]) for w in bench["workloads"]}) \
        == len(bench["workloads"])
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)


def test_every_cell_reports_enough(bench):
    assert any(m["name"] == "setup_s" and "workloads" not in m
               and m["bound"] <= 0.25 for m in bench["end_to_end"])
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        moved = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m.get("workloads", moved)) <= moved
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    for name in cells:
        cell = spec.load_cell(name)
        assert len(cell.end_to_end) >= 2 and cell.per_layer


@pytest.mark.parametrize("name", [w["name"] for w in
                                  spec.load_benchmark()["workloads"]])
def test_cell_files_found_by_name(name):
    cell = spec.load_cell(name)
    assert callable(driver_class(cell.traffic["kind"]))
    assert cell.config["reduced"] is not None
    assert "off_share" in cell.checks
    own = {"ga_refine": {"area_err_max", "stale_share"},
           "sweep": {"repeat_share"}}[cell.traffic["kind"]]
    assert set(cell.checks) == own | {"off_share"}
    for m in cell.per_layer:
        assert callable(spec.metric_reader(m["name"]))


def test_config_files_state_their_cuts(bench):
    for c in bench["configs"]:
        with open(os.path.join(spec.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        for k in c["reduced"]:
            assert k in cfg["published"] and k in cfg["assumed"]


def test_bench_files_are_named_from_names():
    for dirpath, _, files in os.walk(spec.BENCH):
        if "__pycache__" in dirpath:
            continue
        rel = os.path.relpath(dirpath, spec.ROOT)
        for f in files:
            assert PATH.match(os.path.join(rel, f)), f
