"""OverSketch properties: Lemma 6.1 spectral bounds (statistically),
unbiasedness, straggler-drop consistency, chunked streaming equality."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sketch as sk


def _gram_err(key, n, d, cfg, drop=0):
    a = jax.random.normal(key, (n, d)) / np.sqrt(n)
    cs = sk.sample_countsketch(jax.random.fold_in(key, 1), n, cfg)
    at = sk.apply_sketch(cs, a)
    mask = jnp.arange(cfg.total_blocks) >= drop
    h = sk.sketched_gram(at, mask)
    h_true = a.T @ a
    return float(jnp.linalg.norm(h - h_true, 2) / jnp.linalg.norm(h_true, 2))


def test_config_accounting():
    cfg = sk.OverSketchConfig(sketch_dim=2048, block_size=256,
                              straggler_tolerance=0.25)
    assert cfg.num_blocks == 8
    assert cfg.num_redundant == 2
    assert cfg.total_blocks == 10
    assert cfg.total_dim == 2560


def test_config_divisibility():
    with pytest.raises(ValueError):
        sk.OverSketchConfig(sketch_dim=1000, block_size=256)


def test_spectral_approximation_improves_with_sketch_dim():
    """Larger m => smaller eps (Thm 3.1 sketch-dim scaling)."""
    key = jax.random.PRNGKey(0)
    errs = []
    for m, b in [(512, 64), (2048, 256), (8192, 1024)]:
        cfg = sk.OverSketchConfig(m, b, 0.25)
        errs.append(_gram_err(key, 600, 20, cfg))
    assert errs[0] > errs[1] > errs[2]
    assert errs[2] < 0.12


def test_straggler_drop_keeps_accuracy():
    """Dropping <= e blocks with rescale stays comparably accurate."""
    key = jax.random.PRNGKey(1)
    cfg = sk.OverSketchConfig(2048, 256, 0.25)
    full = _gram_err(key, 500, 25, cfg, drop=0)
    dropped = _gram_err(key, 500, 25, cfg, drop=cfg.num_redundant)
    assert dropped < 3 * full + 0.1


def test_unbiasedness():
    """E[S_i S_i^T] = I: the average of many independent block grams -> A^T A."""
    key = jax.random.PRNGKey(2)
    n, d = 200, 10
    a = jax.random.normal(key, (n, d)) / np.sqrt(n)
    cfg = sk.OverSketchConfig(sketch_dim=64 * 64, block_size=64,
                              straggler_tolerance=0.0)
    cs = sk.sample_countsketch(jax.random.fold_in(key, 3), n, cfg)
    h = sk.sketched_gram(sk.apply_sketch(cs, a))
    h_true = a.T @ a
    assert float(jnp.linalg.norm(h - h_true) / jnp.linalg.norm(h_true)) < 0.2


def test_eigenvalue_sandwich():
    """Lemma 6.1: (1-eps) lam_min <= lam(H_hat) <= (1+eps) lam_max, for a
    moderate eps at this sketch size."""
    key = jax.random.PRNGKey(3)
    n, d = 800, 12
    a = jax.random.normal(key, (n, d)) / np.sqrt(n)
    cfg = sk.OverSketchConfig(4096, 512, 0.25)
    h = sk.oversketched_gram(jax.random.fold_in(key, 9), a, cfg)
    ev_true = jnp.linalg.eigvalsh(a.T @ a)
    ev_hat = jnp.linalg.eigvalsh(h)
    eps = 0.5
    assert ev_hat[0] >= (1 - eps) * ev_true[0] - 1e-6
    assert ev_hat[-1] <= (1 + eps) * ev_true[-1] + 1e-6


def test_chunked_apply_matches_full():
    key = jax.random.PRNGKey(4)
    n, d, chunks = 384, 17, 4
    a = jax.random.normal(key, (n, d))
    cfg = sk.OverSketchConfig(256, 64, 0.5)
    cs = sk.sample_countsketch(jax.random.fold_in(key, 5), n, cfg)
    full = sk.apply_sketch(cs, a)
    chunk_rows = n // chunks
    chunked = sk.apply_sketch_chunked(
        cs, lambda c: jax.lax.dynamic_slice_in_dim(a, c * chunk_rows,
                                                   chunk_rows), chunks,
        chunk_rows, d)
    np.testing.assert_allclose(np.asarray(full), np.asarray(chunked),
                               rtol=1e-5, atol=1e-5)


def test_streamed_apply_matches_vmap_and_holds_no_full_tensor():
    """apply_sketch streams blocks through lax.map: bit-for-bit the vmap
    over blocks (kernels/ref.py's oracle), without its (K, n, d) temporary."""
    from repro.kernels import ref
    key = jax.random.PRNGKey(7)
    n, d = 96, 7
    a = jax.random.normal(key, (n, d))
    cfg = sk.OverSketchConfig(256, 64, 0.25)
    cs = sk.sample_countsketch(jax.random.fold_in(key, 8), n, cfg)
    old = ref.count_sketch_apply(cs.h, cs.sigma, a, cs.block_size)
    np.testing.assert_array_equal(np.asarray(sk.apply_sketch(cs, a)),
                                  np.asarray(old))

    full = f"f32[{cfg.total_blocks},{n},{d}]"
    streamed = str(jax.make_jaxpr(sk.apply_sketch)(cs, a))
    vmapped = str(jax.make_jaxpr(
        lambda h, s, x: ref.count_sketch_apply(h, s, x, cs.block_size))(
            cs.h, cs.sigma, a))
    assert full in vmapped          # the check can see the blow-up...
    assert full not in streamed     # ...and the streamed path has none


def test_apply_sketch_takes_segment_sums_off_tpu():
    """Lowered for the CPU, apply_sketch is the lax.map of segment sums,
    bit for bit, jitted or not; the path helper names it so, and names
    the MXU kernel for a TPU."""
    key = jax.random.PRNGKey(11)
    n, d = 300, 13
    a = jax.random.normal(key, (n, d))
    cfg = sk.OverSketchConfig(256, 64, 0.25)
    cs = sk.sample_countsketch(jax.random.fold_in(key, 1), n, cfg)
    segment_sums = jax.jit(lambda h, s, x: jax.lax.map(
        lambda hs: sk.apply_block(hs[0], hs[1], cs.block_size, x), (h, s)))
    expect = np.asarray(segment_sums(cs.h, cs.sigma, a))
    np.testing.assert_array_equal(np.asarray(jax.jit(sk.apply_sketch)(cs, a)),
                                  expect)
    np.testing.assert_array_equal(np.asarray(sk.apply_sketch(cs, a)), expect)
    assert jax.default_backend() == "cpu"
    assert sk.sketch_impl("cpu", cs.block_size) == "segment_sum"
    assert sk.sketch_impl("tpu", cs.block_size) == "mxu_count_sketch"


@pytest.mark.parametrize("block_size", [2048, 4096])
def test_apply_sketch_keeps_segment_sums_for_wide_blocks(block_size):
    """Past MXU_MAX_BLOCK_SIZE the kernel's work per block (which grows
    with b) outruns the segment sums' on a TPU too: apply_sketch is then
    the segment sums on every platform, with no platform switch."""
    assert block_size > sk.MXU_MAX_BLOCK_SIZE
    assert sk.sketch_impl("tpu", block_size) == "segment_sum"
    assert sk.sketch_impl("tpu", sk.MXU_MAX_BLOCK_SIZE) == "mxu_count_sketch"
    key = jax.random.PRNGKey(12)
    n, d = 300, 13
    a = jax.random.normal(key, (n, d))
    cs = sk.sample_countsketch(jax.random.fold_in(key, 1), n,
                               sk.OverSketchConfig(2 * block_size,
                                                   block_size, 0.25))
    text = jax.jit(sk.apply_sketch).lower(cs, a).as_text()
    assert "stablehlo.case" not in text
    np.testing.assert_array_equal(
        np.asarray(sk.apply_sketch(cs, a)),
        np.asarray(sk._apply_segment_sum(cs.h, cs.sigma, a, block_size)))


def test_distributed_gram_matches_local():
    """shard_map masked-psum path == single-device masked gram."""
    mesh = jax.make_mesh((1,), ("model",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    key = jax.random.PRNGKey(5)
    n, d = 256, 9
    a = jax.random.normal(key, (n, d))
    cfg = sk.OverSketchConfig(256, 64, 0.5)
    cs = sk.sample_countsketch(jax.random.fold_in(key, 6), n, cfg)
    surv = jnp.arange(cfg.total_blocks) != 2
    local = sk.sketched_gram(sk.apply_sketch(cs, a), surv)
    dist = sk.distributed_sketched_gram(a, cs, surv, mesh=mesh,
                                        block_axis="model")
    np.testing.assert_allclose(np.asarray(local), np.asarray(dist),
                               rtol=1e-5, atol=1e-5)


def _dropped(cfg, key):
    """A survivor mask that drops e = total - N blocks, as k-of-n does."""
    keep = jax.random.permutation(key, cfg.total_blocks)[:cfg.num_blocks]
    return jnp.zeros((cfg.total_blocks,), bool).at[keep].set(True)


def test_apply_sketch_zeroes_exactly_the_dead_blocks():
    """Off a TPU a live mask leaves the segment sums of the live blocks as
    they are, bit for bit, jitted or not, and zeroes the dead ones."""
    key = jax.random.PRNGKey(21)
    n, d = 300, 13
    a = jax.random.normal(key, (n, d))
    cfg = sk.OverSketchConfig(8 * 64, 64, 0.25)
    cs = sk.sample_countsketch(jax.random.fold_in(key, 1), n, cfg)
    live = _dropped(cfg, jax.random.fold_in(key, 2))
    on = np.asarray(live)
    assert (~on).sum() == cfg.num_redundant == 2
    every = np.asarray(sk.apply_sketch(cs, a))
    for out in (sk.apply_sketch(cs, a, live),
                jax.jit(sk.apply_sketch)(cs, a, live)):
        out = np.asarray(out)
        np.testing.assert_array_equal(out[on], every[on])
        assert not out[~on].any()
    assert every[~on].any()


def test_oversketch_gram_with_survivors_is_the_all_block_formula():
    """The OverSketch family hands the survivor mask to its apply, so the
    dropped blocks are never sketched, and H_hat is still, bit for bit,
    the masked Gram of every block's sketch."""
    from repro import sketching
    key = jax.random.PRNGKey(22)
    n, d = 400, 17
    a = jax.random.normal(key, (n, d)) / np.sqrt(n)
    cfg = sk.OverSketchConfig(10 * 32, 32, 0.25)
    fam = sketching.get("oversketch", cfg)
    cs = fam.sample(jax.random.fold_in(key, 1), n)
    surv = _dropped(cfg, jax.random.fold_in(key, 2))
    on = np.asarray(surv)
    assert (~on).sum() == 3
    assert not np.asarray(fam.apply_live(cs, a, surv))[~on].any()
    formula = jax.jit(lambda c, x, m: sk.sketched_gram(sk.apply_sketch(c, x),
                                                       m))
    for gram, expect in ((fam.gram(cs, a, surv), sk.sketched_gram(
            sk.apply_sketch(cs, a), surv)),
            (jax.jit(fam.gram)(cs, a, surv), formula(cs, a, surv))):
        np.testing.assert_array_equal(np.asarray(gram), np.asarray(expect))


def test_newton_iterates_do_not_depend_on_the_mask_reaching_the_apply(
        monkeypatch):
    """A short solve on the straggler clock: the iterates, and the
    simulated seconds and dollars, are the same bit for bit whether the
    sketch apply skips the dropped blocks or sketches all of them."""
    from repro.core import newton
    from repro.core.objectives import Dataset, LogisticRegression
    from repro.core.straggler import StragglerModel
    from repro.sketching.base import SketchFamily
    from repro.sketching.oversketch import OverSketchFamily
    kx, kw = jax.random.split(jax.random.PRNGKey(23))
    x = jax.random.normal(kx, (600, 12))
    y = jnp.sign(x @ jax.random.normal(kw, (12,)))
    cfg = newton.NewtonConfig(iters=3, coded_block_rows=128,
                              sketch=sk.OverSketchConfig(8 * 32, 32, 0.25))

    def solve():
        newton._jitted_sketched_hessian.cache_clear()
        return newton.oversketched_newton(
            LogisticRegression(lam=1e-3), Dataset(x=x, y=y), jnp.zeros(12),
            cfg, model=StragglerModel(p_tail=0.05, tail_hi=3.0))

    skipped = solve()
    with monkeypatch.context() as mp:
        mp.setattr(OverSketchFamily, "apply_live", SketchFamily.apply_live)
        every = solve()
    newton._jitted_sketched_hessian.cache_clear()
    np.testing.assert_array_equal(np.asarray(skipped.w), np.asarray(every.w))
    for k in ("fval", "gnorm", "time", "cost"):
        assert skipped.history[k] == every.history[k], k
    assert skipped.history["fval"][-1] < skipped.history["fval"][0]
