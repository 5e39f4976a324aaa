"""The harness as data: every cell, configuration, traffic mix and metric of
``BENCHMARK.json`` resolves to files under ``perfbench/``, names and units
keep to their alphabet, each per-layer metric's cells report the
end-to-end metric it moves, and the run command refuses a non-TPU
backend."""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import pytest

import perfbench_tiny
from bench import cell

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = cell.benchmark()


def test_names_and_units_keep_to_their_alphabet():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append(e["name"])
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        assert 0 < len(w["why"]) <= 200 and "\n" not in w["why"]
    for c in BENCH["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
    assert len(set(names)) == len(names)


def test_every_cell_resolves_to_its_files():
    configs = {c["name"]: c for c in BENCH["configs"]}
    for w in BENCH["workloads"]:
        c = configs[w["config"]]
        assert c["file"] == f"perfbench/configs/{w['config']}.json"
        m = cell.config(w["config"])
        assert m["source"] == c["source"] and m["reduced"] == c["reduced"]
        assert os.path.exists(os.path.join(cell.HERE, "configs",
                                           m["reference"]))
        tr = cell.traffic(w["traffic"])
        assert tr["driver"] in ("service_prefill", "token_decode")
        assert tr["correct"]["limits"]
    for pl in BENCH["per_layer"]:
        assert callable(cell.metric_reader(pl["name"]))


def _cells_reporting(metric: dict) -> list:
    return [w["name"] for w in BENCH["workloads"]
            if any(m["name"] == metric["name"]
                   for m in cell.metrics_for(BENCH, w["name"], "per_layer"))]


def test_each_per_layer_metric_moves_a_metric_its_cells_report():
    for pl in BENCH["per_layer"]:
        assert _cells_reporting(pl), pl["name"]
        for w in _cells_reporting(pl):
            reported = [e["name"] for e in
                        cell.metrics_for(BENCH, w, "end_to_end")]
            assert pl["moves"] in reported, (pl["name"], w)
    for w in BENCH["workloads"]:
        e2e = [e["name"] for e in cell.metrics_for(BENCH, w["name"],
                                                    "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.metrics_for(BENCH, w["name"], "per_layer")


def test_layers_name_one_layer_each():
    layers = {pl["layer"] for pl in BENCH["per_layer"]}
    assert layers
    assert all(0 < len(x) <= 200 and "\n" not in x for x in layers)


def test_bounds_are_within_the_contract():
    for e in BENCH["end_to_end"]:
        assert 0.01 <= e["bound"] <= 0.25
        assert e["source"] in ("host_clock", "device_trace")
    setup = [e for e in BENCH["end_to_end"] if e["name"] == "setup_s"]
    assert setup and setup[0]["bound"] <= 0.25
    assert 1 <= BENCH["run_seconds"] <= 51


def test_the_run_command_refuses_a_cpu_backend():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    w = BENCH["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, os.path.join(perfbench_tiny.PERFBENCH, "run.py"),
         "--workload", w, "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=perfbench_tiny.ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_the_judgement_needs_every_number_under_its_limit():
    import run as runner
    ok, compared = runner.judge({"logit_gap": 0.1, "conf_err": 0.5},
                                {"logit_gap": 0.2})
    assert ok and list(compared) == ["logit_gap"]
    assert not runner.judge({"logit_gap": 0.3}, {"logit_gap": 0.2})[0]
    assert not runner.judge({}, {"logit_gap": 0.2})[0]
    assert not runner.judge({"logit_gap": float("nan")},
                            {"logit_gap": 0.2})[0]


@pytest.mark.parametrize("cell_name", ["tiny.prefill", "tiny.decode"])
def test_a_tiny_run_prints_every_metric_of_its_cell(cell_name):
    r = perfbench_tiny.run(cell_name, seed=2 ** 31 + 11)
    b = perfbench_tiny.bench()
    want = {e["name"] for e in cell.metrics_for(b, cell_name, "end_to_end")}
    assert set(r["metrics"]) == want
    assert r["correct"] is True and r["attempted"] > 0
    assert r["compiles_in_window"] == 0
    assert list(r)[-1] == "compared"
    json.dumps(r)
