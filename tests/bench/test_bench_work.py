"""The benchmark's work counts and peaks table, checked by hand at both
cells' shapes."""
import json
import os

import pytest

from bench import work
from conftest import REPO


def _config(name):
    with open(os.path.join(REPO, "bench", "configs", name + ".json")) as f:
        return json.load(f)


def _sketch(config):
    """The sketch of a configuration, given by name or as its dict."""
    if isinstance(config, str):
        config = _config(config)
    return config["newton"]["sketch"]


def _peak():
    return work.peaks("TPU v5 lite")


def test_sketch_blocks_follow_the_fig7_and_fig8_sketches():
    assert work.sketch_blocks(_sketch("epsilon")) == (148, 118, 256)
    assert work.sketch_blocks(_sketch("a9a")) == (13, 10, 128)


def test_sketch_blocks_agree_with_the_program():
    from repro.core import OverSketchConfig
    for name in ("epsilon", "a9a"):
        sk = _sketch(name)
        cfg = OverSketchConfig(**sk)
        assert work.sketch_blocks(sk) == (cfg.total_blocks, cfg.num_blocks,
                                          cfg.block_size)


def test_epsilon_hessian_is_one_read_of_a_and_memory_bound():
    c = _config("epsilon")
    w = work.hessian(c["n"], c["d"], *work.sketch_blocks(_sketch(c)))
    assert w.bytes == 4 * 200_000 * 2000 + 4 * 2000 * 2000   # 1.6 GB
    assert w.bytes == pytest.approx(1.6e9, rel=0.02)
    # K n d scatter + 2 N b d^2 Gram + 3 n d for hess_sqrt
    assert w.ops == 148 * 200_000 * 2000 + 2 * 118 * 256 * 2000 ** 2 \
        + 3 * 200_000 * 2000
    t, bound = work.least_time(w, _peak())
    assert bound == "memory"
    assert t == pytest.approx(1.95e-3, rel=0.02)


def test_a9a_hessian_least_time():
    c = _config("a9a")
    w = work.hessian(c["n"], c["d"], *work.sketch_blocks(_sketch(c)))
    assert w.bytes == pytest.approx(15.7e6, rel=0.01)
    t, bound = work.least_time(w, _peak())
    assert bound == "memory"
    assert t == pytest.approx(19e-6, rel=0.03)


def test_epsilon_iteration_reads_x_four_times():
    c = _config("epsilon")
    w = work.iteration(c["n"], c["d"], *work.sketch_blocks(_sketch(c)))
    assert w.bytes == pytest.approx(6.4e9, rel=0.01)
    t, bound = work.least_time(w, _peak())
    assert bound == "memory"
    assert t == pytest.approx(7.8e-3, rel=0.02)


def test_an_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("cpu")
    with pytest.raises(KeyError):
        work.peaks("TPU v6 lite")


def test_every_peak_names_its_source():
    with open(work.PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    for kind, row in table.items():
        assert row["flops_per_s"] > 0 and row["bytes_per_s"] > 0, kind
        assert "TPU v5e" in row["source"], kind
