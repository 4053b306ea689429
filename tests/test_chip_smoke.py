"""chip_smoke.py on CPU: its phase function at a tiny size with the same
checks (the segment sums in place of the chip's kernel), its compile-cache
rule, and main()'s refusal to run without a TPU."""
import os
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)            # chip_smoke.py lives at the repo root

import chip_smoke  # noqa: E402
from repro import sketching  # noqa: E402
from repro.core import OverSketchConfig  # noqa: E402

TINY = chip_smoke.Phase("tiny", 600, 20, 100, OverSketchConfig(256, 64, 0.25),
                        coded_block_rows=64, iters=6)


def test_tiny_phase_passes_every_check_on_cpu():
    out = chip_smoke.run_phase(TINY, seed=0)
    assert out["sketch_impl"] == "segment_sum"
    assert out["hessian_rel"] <= chip_smoke.HESSIAN_RTOL
    assert len(out["fvals"]) == TINY.iters
    assert out["vs_ref_rel"] <= chip_smoke.REF_REL
    # On the CPU the Hessian lowers to plain HLO: main() would refuse this.
    assert out["custom_call"] is False


def test_a_failed_check_raises(monkeypatch):
    monkeypatch.setattr(chip_smoke, "HESSIAN_RTOL", -1.0)
    short = chip_smoke.Phase("tiny", 600, 20, 100,
                             OverSketchConfig(256, 64, 0.25), 64, iters=1)
    with pytest.raises(AssertionError, match="Hessian rel err"):
        chip_smoke.run_phase(short, seed=0)


@pytest.mark.parametrize("name,blocks", [("epsilon", 148), ("a9a", 13)])
def test_phases_at_published_widths(name, blocks):
    """The published sketches, whose blocks the MXU kernel sketches on a
    TPU."""
    ph = {p.name: p for p in chip_smoke.PHASES}[name]
    assert ph.sketch.total_blocks == blocks
    fam = sketching.get("oversketch", ph.sketch)
    assert fam.apply_path("tpu") == "mxu_count_sketch"


def test_compile_cache_dir_rule(tmp_path):
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)}
    assert chip_smoke.compile_cache_dir(env) == str(tmp_path)
    assert chip_smoke.compile_cache_dir({}) == os.path.join(REPO,
                                                            ".jax_cache")


def test_main_refuses_a_non_tpu_device(capsys):
    saved = jax.config.jax_compilation_cache_dir
    try:
        assert chip_smoke.main([]) != 0
    finally:
        jax.config.update("jax_compilation_cache_dir", saved)
    out = capsys.readouterr()
    assert out.out == ""               # no result line without a chip
    assert "needs a TPU" in out.err
