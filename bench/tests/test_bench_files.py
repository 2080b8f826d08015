"""BENCHMARK.json keeps the contract, and every file it names loads by
name."""
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bm():
    return harness.load_benchmark(ROOT)


def test_keys_and_names(bm):
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert bm["command"] == ["python3", "bench/run.py"]
    assert bm["paths"] == ["bench"]
    assert 1 <= bm["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bm[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert len(json.dumps(bm)) < 64 * 1024


def test_bounds_and_moves(bm):
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bm["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in bm["workloads"]}
    for m in bm["per_layer"]:
        assert m["moves"] in e2e
        for w in m["workloads"]:
            assert w in cells
            assert w in e2e[m["moves"]].get("workloads", [w])


@pytest.mark.parametrize("kind", ("config", "traffic"))
def test_every_cell_loads(bm, kind):
    for w in bm["workloads"]:
        c = harness.cell(bm, w["name"], ROOT)
        assert c["chips"] in (1, 4)
        if kind == "config":
            assert c["config"]["system"] in ("serving", "allocator")
        else:
            assert c["mix"]["loop"] in ("closed", "open", "paper_iter")


def test_every_metric_has_a_reader(bm):
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert callable(harness.reader(m["name"]))


def test_every_cell_reports_setup_another_e2e_and_a_layer(bm):
    for w in bm["workloads"]:
        e2e = [m["name"] for m in harness.metrics_for(bm, w["name"], False)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert harness.metrics_for(bm, w["name"], True)


def test_config_files_are_the_program_arch(bm):
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.configs import get_arch
    from bench import serving
    for c in bm["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        if cfg["system"] == "serving":
            serving.check_arch(get_arch(cfg["arch"]), cfg)


def test_serving_configs_name_their_modules(bm):
    """Each serving configuration names a reference module that declares
    the keys it reads and its control, an operation count, and weight
    rules of known kinds; its engine keys and its cells' are arguments
    of the program's engine."""
    import importlib
    import inspect
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from bench import serving
    from repro.serve.engine import ServingEngine
    args = set(inspect.signature(ServingEngine).parameters)
    for w in bm["workloads"]:
        c = harness.cell(bm, w["name"], ROOT)
        cfg = c["config"]
        if cfg["system"] != "serving":
            continue
        ref, sizes = serving.reference(cfg)
        assert set(sizes) == set(ref.SIZES) <= set(cfg)
        assert callable(ref.served_gaps) and ref.CONTROL
        flops = importlib.import_module("bench." + cfg["flops"])
        assert flops.prefill_flops(cfg, 16) > flops.decode_token_flops(
            cfg, 16) > 0
        assert set(serving.init_rules(cfg)) >= set(serving.RULES)
        assert set(cfg["engine"]) | set(c["mix"]["engine"]) <= args
