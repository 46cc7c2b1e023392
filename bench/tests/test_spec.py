"""``BENCHMARK.json`` keeps to its contract, and every cell resolves by
name alone to its configuration, traffic mix, limits, reference and
per-layer reducers."""

import json
import os
import re

import pytest

from bench import spec

ROOT = spec.ROOT
BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TEXT_KEYS = ("why", "layer", "source")
CELLS = [w["name"] for w in BENCH["workloads"]]


def _one_line(text):
    return (isinstance(text, str) and 1 <= len(text) <= 200
            and "\n" not in text and "\t" not in text)


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    # a full check of 24 cells fits its time
    rs = BENCH["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_configs():
    assert 1 <= len(BENCH["configs"]) <= 24
    names = [c["name"] for c in BENCH["configs"]]
    assert len(set(names)) == len(names)
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _one_line(c["source"])
        assert _one_line(c["why"]) and c["name"] in used
        assert c["file"].startswith("bench/")
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        assert set(cfg["reduced"]) == set(c["reduced"])
        assert cfg["assumed"]


def test_workloads():
    ws = BENCH["workloads"]
    assert 1 <= len(ws) <= 24
    assert len(set(CELLS)) == len(CELLS)
    pairs = [(w["config"], w["traffic"]) for w in ws]
    assert len(set(pairs)) == len(pairs)
    for w in ws:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _one_line(w["why"])
    four = sum(w["chips"] == 4 for w in ws)
    assert four <= max(1, len(ws) // 2)


def test_metrics():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    names = list(e2e) + [m["name"] for m in BENCH["per_layer"]]
    assert len(set(names)) == len(names)
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {}
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert _one_line(m["layer"])
        assert m["moves"] in e2e
        for cell in m["workloads"]:
            assert cell in CELLS
            moved = e2e[m["moves"]]
            assert "workloads" not in moved or cell in moved["workloads"]
        layers.setdefault(m["layer"], []).append(m["name"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert all(cell in CELLS for cell in m.get("workloads", CELLS))
    for cell in CELLS:
        got = [m["name"] for m in spec.resolve(cell).end_to_end]
        assert "setup_s" in got and len(got) >= 2
        assert spec.resolve(cell).per_layer


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_from_data_alone(cell):
    c = spec.resolve(cell)
    assert c.chips == next(w["chips"] for w in BENCH["workloads"]
                           if w["name"] == cell)
    ndim = c.config["program"]["ndim"]
    assert len(c.config["grid"]) == ndim
    assert int(c.traffic["steps_per_call"][str(ndim)]) >= 1
    assert isinstance(c.limits["checked"], int) and c.limits["checked"] >= 1
    assert set(c.limits["limits"]) >= {"grid_rel_err"}
    if c.traffic.get("receiver_rows"):
        assert "receiver_rel_err" in c.limits["limits"]
    if c.chips > 1:
        assert len(c.config["reference_mesh"]) == ndim
    ref = spec.reference(c.config)
    assert callable(ref.advance_fn) and callable(ref.offsets)
    for m in c.per_layer:
        assert callable(spec.reducer(m["name"]))


def test_every_file_is_named_from_a_name():
    for dirpath, _, files in os.walk(os.path.join(ROOT, "bench")):
        if ".cache" in dirpath or ".traces" in dirpath or \
                "__pycache__" in dirpath:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(dirpath, f), ROOT)
            assert re.match(r"^[A-Za-z0-9_./-]+$", rel), rel


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        spec.resolve("no_such_config.long")


def test_qualified_metric_is_its_quantity():
    assert spec.quantity("gcells_per_s.step1") == "gcells_per_s"
    assert spec.quantity("call_p95_ms") == "call_p95_ms"
    assert (spec.reducer("kernel_roofline_pct.step1").__code__.co_filename
            == spec.reducer("kernel_roofline_pct").__code__.co_filename)
    # a metric with a file of its own keeps it
    assert spec.reducer("setup.plan_s").__code__.co_filename.endswith(
        os.path.join("metrics", "setup.plan_s.py"))
    with pytest.raises(FileNotFoundError):
        spec.reducer("no_such_metric")
