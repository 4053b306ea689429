"""Fixtures for the benchmark's CPU tests: a scratch checkout that holds the
benchmark's files, the program, and one tiny cell added as new files."""
import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

TINY_CONFIG = {
    "name": "tiny", "objective": "logistic", "lam": 1e-05,
    "n": 1536, "d": 24, "n_test": 0, "cond": 10.0, "sorted_layout": True,
    "precision": "float32",
    "newton": {"sketch": {"sketch_dim": 512, "block_size": 64,
                          "straggler_tolerance": 0.25},
               "coded_block_rows": 64},
}
TINY_LIMITS = {"f_gap": {"limit": 1e-3}, "f_report": {"limit": 1e-4}}


def add_cell(root, name="tiny-logistic", config=TINY_CONFIG,
             traffic=("solves3", {"loop": "closed", "iters": 3}),
             limits=TINY_LIMITS, metric=None, moves="solve_s"):
    """Add a configuration, a traffic mix, a cell (and optionally a
    per-layer metric) as new files plus entries in BENCHMARK.json; the
    cell reports the end-to-end metric ``moves``."""
    bench_dir = os.path.join(root, "bench")
    with open(os.path.join(bench_dir, "configs",
                           config["name"] + ".json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(bench_dir, "traffic", traffic[0] + ".json"),
              "w") as f:
        json.dump(traffic[1], f)
    with open(os.path.join(bench_dir, "workloads", name + ".json"),
              "w") as f:
        json.dump({"limits": limits}, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": config["name"], "source": "test",
        "file": f"bench/configs/{config['name']}.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({"name": name, "config": config["name"],
                               "traffic": traffic[0], "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == moves:
            m["workloads"].append(name)
    if metric is not None:
        metric_name, source = metric
        if source is not None:
            with open(os.path.join(bench_dir, "metrics",
                                   metric_name + ".py"), "w") as f:
                f.write(source)
        bench["per_layer"].append({
            "name": metric_name, "unit": "ms", "better": "lower",
            "source": "device_trace", "layer": "test", "moves": moves,
            "workloads": [name]})
    with open(path, "w") as f:
        json.dump(bench, f)
    return name


@pytest.fixture
def checkout(tmp_path):
    """A copy of BENCHMARK.json and the benchmark's paths, with the program
    linked in as ``src``."""
    root = str(tmp_path / "checkout")
    os.makedirs(root)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(REPO, "src"), os.path.join(root, "src"))
    return root


@pytest.fixture
def cpu_chip(monkeypatch):
    """Skip the harness's look for a TPU: the CPU stands in for one chip.
    Its kind names a row of the peaks table so that a traced run can look
    its peaks up; a CPU trace has no device plane, so no device metric
    reads anything from it."""
    from bench import run
    monkeypatch.setattr(run, "require_chip", lambda chips: {
        "platform": "cpu", "kind": "TPU v5 lite", "count": chips})
    return run


@pytest.fixture
def harness(cpu_chip, monkeypatch, capsys):
    """Call ``bench/run.py``'s main on the CPU and return (exit code, the
    result's last line parsed or None, standard error).  The persistent
    compile cache stays off and JAX's settings are restored after."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(cpu_chip, "compile_cache_dir", lambda env, root: None)
    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs)

    def call(root, workload, seed=5, seconds=0.3, trace=0, newton=None):
        rc = cpu_chip.main(["--workload", workload, "--seed", str(seed),
                            "--seconds", str(seconds), "--trace", str(trace)],
                           root=root, newton=newton)
        out, err = capsys.readouterr()
        lines = out.strip().splitlines()
        return rc, (json.loads(lines[-1]) if lines else None), err

    yield call
    jax.config.update("jax_compilation_cache_dir", saved[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", saved[1])
    compilation_cache.reset_cache()
