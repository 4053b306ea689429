"""The harness on the CPU: cells, configurations and metrics found by name,
the result line's keys, refusals without a chip or without the program,
and BENCHMARK.json against the benchmark contract's rules."""
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import cell as cells, check
from conftest import REPO, TINY_CONFIG, add_cell

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "breakdown", "check"}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
COUNTER = "def read(ctx):\n    return float(ctx.iterations)\n"


def _hashes(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            if "__pycache__" not in p and not os.path.islink(p):
                with open(p, "rb") as fh:
                    out[os.path.relpath(p, root)] = hashlib.sha256(
                        fh.read()).hexdigest()
    return out


def test_new_files_are_found_by_name(checkout, harness):
    before = _hashes(os.path.join(checkout, "bench"))
    name = add_cell(checkout, metric=("iterations_seen", COUNTER))
    after = _hashes(os.path.join(checkout, "bench"))
    assert all(after[p] == h for p, h in before.items())   # none edited

    rc, res, err = harness(checkout, name, trace=0)
    assert rc == 0 and res["correct"] is True, err
    assert set(res["metrics"]) == {"solve_s", "setup_s"}
    assert res["attempted"] >= 1 and res["failed"] == 0
    tail = err.strip().splitlines()[-len(check.NUMBERS):]
    for k, line in zip(check.NUMBERS, tail):
        assert line.startswith(f"{k} ") and " limit " in line

    rc, res, err = harness(checkout, name, trace=1)
    assert rc == 0 and res["correct"] is True, err
    # The CPU shows no device plane: the device metrics read nothing and
    # are left out, the new metric reads the iterations it was given.
    assert res["metrics"] == {"iterations_seen": {
        "value": 3.0 * res["attempted"], "unit": "ms"}}
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_variants_report_their_quantity_under_their_own_name(checkout,
                                                            harness):
    """``solve_s.host`` reports the solve time under its own bound, and a
    per-layer ``<quantity>.<variant>`` with no file of its own is read by
    the quantity's reader."""
    with open(os.path.join(checkout, "bench", "metrics",
                           "iterations_seen.py"), "w") as f:
        f.write(COUNTER)
    name = add_cell(checkout, metric=("iterations_seen.host", None),
                    moves="solve_s.host")
    rc, res, err = harness(checkout, name, trace=0)
    assert rc == 0 and res["correct"] is True, err
    assert set(res["metrics"]) == {"solve_s.host", "setup_s"}
    rc, res, err = harness(checkout, name, trace=1)
    assert rc == 0 and res["correct"] is True, err
    assert res["metrics"] == {"iterations_seen.host": {
        "value": 3.0 * res["attempted"], "unit": "ms"}}


def test_a_new_objective_is_found_by_name(checkout, harness):
    objectives = os.path.join(checkout, "bench", "objectives")
    shutil.copy(os.path.join(objectives, "logistic.py"),
                os.path.join(objectives, "logistic_copy.py"))
    config = dict(TINY_CONFIG, name="tiny_copy", objective="logistic_copy")
    name = add_cell(checkout, name="tiny-copy", config=config)
    assert cells.load(checkout, name).objective.__name__.endswith(
        "logistic_copy")
    rc, res, err = harness(checkout, name)
    assert rc == 0 and res["correct"] is True, err


@pytest.mark.parametrize("what, change", [
    ("traffic", {"loop": "open"}),
    ("config", {"precision": "bfloat16"}),
    ("config", {"n_test": 100}),
    ("config", {"newton": {"use_kernels": True}}),
    ("config", {"newton": {"iters": 4}}),
], ids=["open-loop", "precision", "test-set", "use_kernels", "iters"])
def test_what_the_harness_cannot_run_is_refused(checkout, what, change):
    traffic = {"loop": "closed", "iters": 3}
    config = dict(TINY_CONFIG)
    (traffic if what == "traffic" else config).update(change)
    name = add_cell(checkout, config=config, traffic=("mix", traffic))
    with pytest.raises(ValueError):
        cells.load(checkout, name)


def test_the_last_line_has_only_the_contract_keys(checkout, harness):
    name = add_cell(checkout)
    for trace in (0, 1):
        rc, res, _ = harness(checkout, name, trace=trace)
        assert rc == 0
        assert set(res) <= RESULT_KEYS
        assert {"correct", "attempted", "failed", "metrics",
                "device"} <= set(res)
        assert list(res)[-1] == "check"
        assert set(res["check"]) == set(check.NUMBERS)
        for v in res["check"].values():
            assert set(v) == {"value", "limit"}
        for m in res["metrics"].values():
            assert set(m) == {"value", "unit"}
        assert {"platform", "kind", "count",
                "memory_peak_bytes"} <= set(res["device"])
        if trace:
            assert {"busy_s", "window_s"} <= set(res["device"])
        else:
            assert "breakdown" not in res


def test_a_wrong_answer_is_not_correct(checkout, harness):
    from repro.core import NewtonResult
    name = add_cell(checkout)

    def unmoved(objective, data, w0, cfg):
        return NewtonResult(w=w0, history={
            "fval": [float(objective.value(w0, data))]})

    rc, res, err = harness(checkout, name, newton=unmoved)
    assert rc == 0 and res["correct"] is False
    assert res["failed"] == res["attempted"]
    assert res["check"]["f_gap"]["value"] > res["check"]["f_gap"]["limit"]


def test_run_exits_nonzero_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "a9a-logistic",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_a_checkout_without_the_program_exits_nonzero(tmp_path):
    root = tmp_path / "bare"
    root.mkdir()
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        paths = json.load(f)["paths"]
    for p in paths:
        shutil.copytree(os.path.join(REPO, p), root / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "a9a-logistic",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


# ---- BENCHMARK.json against the contract's rules ----

@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_has_exactly_the_contract_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "bench/run.py"]
    assert 1 <= bench["run_seconds"] <= 51
    for p in bench["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p
        assert os.path.isdir(os.path.join(REPO, p))


def test_every_name_and_unit_uses_the_allowed_characters(bench):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group, entry["name"]))
    for w in bench["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    for c in bench["configs"]:
        for k in c["reduced"]:
            assert NAME.match(k), k
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    metric_names = [m["name"] for m in bench["end_to_end"]
                    + bench["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    for text in [e["why"] for e in bench["configs"] + bench["workloads"]] \
            + [m["layer"] for m in bench["per_layer"]]:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_every_entry_has_its_files(bench):
    names = {w["name"] for w in bench["workloads"]}
    assert {w["config"] for w in bench["workloads"]} == {
        c["name"] for c in bench["configs"]}
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(REPO, c["file"]))
        assert c["file"].startswith("bench/configs/")
    for w in bench["workloads"]:
        assert w["chips"] in (1, 4)
        assert os.path.isfile(os.path.join(REPO, "bench", "traffic",
                                           w["traffic"] + ".json"))
        with open(os.path.join(REPO, "bench", "workloads",
                               w["name"] + ".json")) as f:
            limits = json.load(f)["limits"]
        assert set(limits) == set(check.NUMBERS)
        # Each limit lies between the readings it was set from.
        for k, v in limits.items():
            assert v["lower"] < v["limit"] < v["upper"], (w["name"], k)
    reported = {}
    for e in bench["end_to_end"]:
        for w in e.get("workloads", names):
            reported.setdefault(w, set()).add(e["name"])
    for m in bench["per_layer"]:
        metrics = os.path.join(REPO, "bench", "metrics")
        assert os.path.isfile(os.path.join(metrics, m["name"] + ".py")) \
            or os.path.isfile(os.path.join(metrics,
                                           m["name"].split(".")[0] + ".py"))
        # Every cell the metric lists reports the metric it moves.
        for w in m.get("workloads", names):
            assert w in names and m["moves"] in reported[w], (m["name"], w)
    for w in names:
        assert "setup_s" in reported[w] and len(reported[w]) >= 2, w
        assert any(w in m.get("workloads", names)
                   for m in bench["per_layer"]), w
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in e2e.values():
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def test_a_full_check_fits_its_time_with_24_cells(bench):
    rs = bench["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200
