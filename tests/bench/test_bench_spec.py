"""``BENCHMARK.json``: every piece it names is found by name, a piece added
as new files only is picked up, and the file keeps to its schema."""
import json
import re

import pytest

from bench import harness
from bench.spec import ROOT, Benchmark
from benchtools import tiny_tree

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return Benchmark()


def test_every_named_file_is_found(bench):
    spec = bench.spec
    for c in spec["configs"]:
        cfg = bench.config(c["name"])
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
    for w in spec["workloads"]:
        bench.config(w["config"])
        assert bench.traffic(w["traffic"])["loop"] in ("open", "closed")
        assert set(bench.check(w["name"])["limits"]) == {"logit_err", "wrong_outputs", "missing"}
    for m in spec["per_layer"]:
        assert callable(bench.reader(m["name"]))


def test_end_to_end_names_are_the_harness_s(bench):
    names = {m["name"] for m in bench.spec["end_to_end"]}
    window = harness.Window(start=0.0, end=1.0, requests=[
        harness.Request(tasks=(0,), prompt=0, start=0.0, done=0.5)],
        counters={}, compiles=0, chips=1, peak={})
    assert names == set(harness.end_to_end(window, 1.0))


def test_schema(bench):
    spec = bench.spec
    assert set(spec) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert spec["command"] == ["python3", "bench/run.py"]
    assert 1 <= spec["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in spec[k]]
    assert len(set(names)) == len(names) and all(NAME.match(n) for n in names)
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        cfg = json.loads((ROOT / c["file"]).read_text())
        for key in c["reduced"]:
            assert cfg[key] != cfg["published"][key]
    pairs = [(w["config"], w["traffic"]) for w in spec["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"} and w["chips"] == 1
        assert 1 <= len(w["why"]) <= 200
    assert "setup_s" in {m["name"] for m in spec["end_to_end"]}
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace") and 0 < m["bound"] <= 0.25
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    e2e = {m["name"] for m in spec["end_to_end"]}
    cells = {w["name"] for w in spec["workloads"]}
    for m in spec["per_layer"]:
        assert m["moves"] in e2e and set(m["workloads"]) <= cells
        assert UNIT.match(m["unit"]) and "\n" not in m["layer"]
    for w in cells:
        assert bench.per_layer(w), f"{w} reports no per-layer metric"


def test_an_end_to_end_metric_with_a_cell_list_is_reported_there_only(bench):
    for w in bench.spec["workloads"]:
        reported = {m["name"] for m in bench.end_to_end(w["name"])}
        for m in bench.spec["end_to_end"]:
            assert (m["name"] in reported) == (w["name"] in m.get("workloads", [w["name"]]))
        assert {"requests_per_s", "setup_s"} <= reported
        # A per-layer metric is listed only where what it moves is reported.
        for m in bench.spec["per_layer"]:
            if w["name"] in m["workloads"]:
                assert m["moves"] in reported, (m["name"], w["name"])


def test_new_files_alone_add_a_config_traffic_and_metric(tmp_path):
    tiny = tiny_tree(tmp_path, extra_metric=True)
    assert tiny.config("tiny-t4")["hidden_size"] == 64
    assert tiny.traffic("tiny-open")["loop"] == "open"
    assert "requests_done" in {m["name"] for m in tiny.per_layer("tiny-t4.open")}
    assert tiny.reader("requests_done")(type("W", (), {"completed": [1, 2]})()) == 2.0
