"""BENCHMARK.json against the files the harness finds by its names."""

import collections
import importlib
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import cell as C  # noqa: E402

BENCH = C.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_every_file_of_a_cell_is_found(workload):
    spec = C.load_cell(workload)
    assert spec["config"]["chips"] == spec["chips"]
    for key in ("source", "reduced", "assumed", "guarantees", "connector"):
        assert spec["config"][key], key
    ends = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in ends and len(ends) >= 2 and spec["per_layer"]
    for m in spec["end_to_end"]:
        assert C.load_metric_file("end_to_end", m["name"])["function"]
    for m in spec["per_layer"]:
        f = C.load_metric_file("layer_metrics", m["name"])
        assert (f["layer"], f["unit"]) == (m["layer"], m["unit"])
        # the arrow stands in BENCHMARK.json alone: to a metric the
        # cell reports
        assert m["moves"] in ends and "moves" not in f
        assert callable(importlib.import_module(
            f"benchmark.readers.{f['reader']}").read)
    for name, t in spec["templates"].items():
        refs = importlib.import_module(
            f"benchmark.reference.{t['suite']}").REFERENCES
        assert t["reference"] in refs
        assert set(t["scans"]) <= set(t["reads"])
        for p in C.pairs(spec["traffic"]):
            if p[0] == name:
                sql = C.render_sql(t, C.binding(spec["traffic"], *p))
                assert "{" not in sql and "select" in sql.lower()


@pytest.mark.parametrize("workload", CELLS)
def test_every_seed_gives_the_same_work_in_another_order(workload):
    traffic = C.load_cell(workload)["traffic"]
    want = collections.Counter(C.pairs(traffic))
    seen = set()
    for seed in (0, 7, 2**31 + 12345):
        orders = C.stream_orders(traffic, seed)
        assert len(orders) == traffic["streams"] and all(orders)
        # dealt round the streams: together the whole mix, and no
        # statement in two streams (the server coalesces those)
        assert sum((collections.Counter(o) for o in orders),
                   collections.Counter()) == want
        assert max(want.values()) == 1
        assert C.stream_orders(traffic, seed) == orders      # from the seed
        seen.add(str(orders))
    if traffic["order"] == "shuffle":
        assert len(seen) > 1


def test_pairs_are_dealt_round_the_streams():
    traffic = {"streams": 2, "order": "as_listed", "templates": [
        {"template": "a", "bindings": [{"x": 1}, {"x": 2}, {"x": 3}]},
        {"template": "b"}]}
    assert C.stream_orders(traffic, 3) == [
        [("a", 0), ("a", 2)], [("a", 1), ("b", 0)]]
    with pytest.raises(ValueError, match="pairs to deal"):
        C.stream_orders(dict(traffic, streams=5), 3)


def test_a_variant_is_read_as_its_quantity_is():
    """``<quantity>.<variant>`` (the contract's split of a quantity
    whose cells report different end-to-end metrics) has no file of its
    own: the entries share the measurement."""
    variants = [m["name"] for m in BENCH["per_layer"] if "." in m["name"]]
    assert variants
    for name in variants:
        assert C.load_metric_file("layer_metrics", name) == \
            C.load_metric_file("layer_metrics", name.rsplit(".", 1)[0])
    with pytest.raises(FileNotFoundError):
        C.load_metric_file("layer_metrics", "no_such.metric")


@pytest.mark.parametrize("workload", CELLS)
def test_the_configurations_env_is_applied(workload, monkeypatch):
    env = C.load_cell(workload)["config"]["env"]
    assert env and all(k in C.load_cell(workload)["config"]["assumed"]["env"]
                       for k in env)
    for form in (["--seed", "1", "--workload", workload],
                 [f"--workload={workload}"]):
        for k in env:
            monkeypatch.setenv(k, "as the test found it")
        assert C.apply_env(form) == env
        assert all(os.environ[k] == v for k, v in env.items())
    assert C.apply_env(["--workload", "no_such_cell"]) == {}


def test_unknown_names_are_errors():
    with pytest.raises(KeyError, match="unknown workload"):
        C.load_cell("no_such_cell")
    with pytest.raises(ValueError, match="order rule"):
        C.stream_orders({"streams": 1, "order": "zigzag", "templates": [
            {"template": "x"}]}, 1)


def test_paths_and_layers():
    assert BENCH["command"] == ["python3", "benchmark/run.py"]
    for p in BENCH["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    by_layer = collections.defaultdict(set)
    for m in BENCH["per_layer"]:
        by_layer[m["layer"]].add(m["name"])
    assert "device" in by_layer and "kernels" in by_layer
