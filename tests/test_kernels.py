"""The count-sketch Pallas kernel and the Hessian's Gram path against the
pure-jnp oracles of ``kernels/ref.py``: shape/dtype sweeps + hypothesis.

The kernel runs in interpret mode on CPU (the TPU-target validation
path); the families' Grams run the jnp code the solve runs on CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro import sketching
from repro.core import sketch as core_sketch
from repro.core.sketch import OverSketchConfig, sketched_gram
from repro.kernels import count_sketch, ref


# ---------------------------------------------------------------- count sketch
@pytest.mark.parametrize("k,n,d,b", [
    (1, 64, 32, 64),
    (3, 300, 70, 128),
    (5, 1000, 17, 256),     # ragged d
    (2, 129, 130, 64),      # ragged both
])
def test_count_sketch_shapes(k, n, d, b):
    key = jax.random.PRNGKey(k * 100 + n)
    kh, ks, ka = jax.random.split(key, 3)
    h = jax.random.randint(kh, (k, n), 0, b, dtype=jnp.int32)
    sigma = jax.random.rademacher(ks, (k, n), dtype=jnp.float32)
    a = jax.random.normal(ka, (n, d))
    out = count_sketch.count_sketch_apply(h, sigma, a, b, interpret=True)
    expect = ref.count_sketch_apply(h, sigma, a, b)
    assert out.shape == (k, b, d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_count_sketch_dtypes(dtype):
    key = jax.random.PRNGKey(7)
    kh, ks, ka = jax.random.split(key, 3)
    k, n, d, b = 2, 128, 64, 64
    h = jax.random.randint(kh, (k, n), 0, b, dtype=jnp.int32)
    sigma = jax.random.rademacher(ks, (k, n), dtype=jnp.float32)
    a = jax.random.normal(ka, (n, d)).astype(dtype)
    out = count_sketch.count_sketch_apply(h, sigma, a, b, interpret=True)
    expect = ref.count_sketch_apply(h, sigma, a.astype(jnp.float32), b)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=tol, atol=tol)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 1000), n=st.integers(8, 200),
       d=st.integers(1, 100))
def test_count_sketch_property(seed, n, d):
    b = 64
    key = jax.random.PRNGKey(seed)
    kh, ks, ka = jax.random.split(key, 3)
    h = jax.random.randint(kh, (2, n), 0, b, dtype=jnp.int32)
    sigma = jax.random.rademacher(ks, (2, n), dtype=jnp.float32)
    a = jax.random.normal(ka, (n, d))
    out = count_sketch.count_sketch_apply(h, sigma, a, b, interpret=True)
    expect = ref.count_sketch_apply(h, sigma, a, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-4, atol=1e-4)


# The MXU kernel's grid at the shapes it has to handle: K not a multiple of
# the block group (13, 148), n not a multiple of the row panel, ragged d,
# each block size the configurations use, and d = 8192 (16 column tiles).
@pytest.mark.parametrize("k,n,d,b", [
    (13, 1100, 123, 128),
    (13, 300, 130, 256),
    (148, 1100, 130, 64),
    (148, 2500, 123, 256),
    (148, 1100, 123, 128),
    (13, 2500, 130, 64),
    (1, 64, 8192, 64),
])
def test_count_sketch_grid_matches_segment_sum(k, n, d, b):
    kh, ks, ka = jax.random.split(jax.random.PRNGKey(k + n + d + b), 3)
    h = jax.random.randint(kh, (k, n), 0, b, dtype=jnp.int32)
    sigma = jax.random.rademacher(ks, (k, n), dtype=jnp.float32)
    a = jax.random.normal(ka, (n, d))
    out = count_sketch.count_sketch_apply(h, sigma, a, b, interpret=True)
    expect = ref.count_sketch_apply(h, sigma, a, b)
    assert out.shape == (k, b, d) and out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


def test_count_sketch_keeps_all_24_bits():
    """Every entry needs the whole f32 significand.  One bfloat16 pass
    drops its last 2^-12 (2.4e-4 relative); the three-part split keeps
    it, so the three passes agree with the segment sum to 1e-6."""
    k, n, d, b = 13, 1100, 130, 128
    kh, ks = jax.random.split(jax.random.PRNGKey(24))
    h = jax.random.randint(kh, (k, n), 0, b, dtype=jnp.int32)
    sigma = jax.random.rademacher(ks, (k, n), dtype=jnp.float32)
    a = jnp.full((n, d), 1 + 2 ** -12 + 2 ** -20, jnp.float32)
    expect = np.asarray(ref.count_sketch_apply(h, sigma, a, b))

    def rel(x):
        return np.abs(np.asarray(x) - expect).max() / np.abs(expect).max()

    assert rel(count_sketch.count_sketch_apply(h, sigma, a, b,
                                               interpret=True)) <= 1e-6
    one_pass = count_sketch.count_sketch_apply(
        h, sigma, a.astype(jnp.bfloat16), b, interpret=True)
    assert rel(one_pass) > 1e-4


# The blocks the straggler mask drops at each K = N + e: e of OverSketch's
# provisioning for N = 10, 118 and 16 survivors (3 of 13, 30 of 148, 4 of 20).
_DROPPED = {c.total_blocks: c.num_redundant
            for c in (OverSketchConfig(n, 1) for n in (10, 118, 16))}


def _live_mask(kind, k, key):
    """all: every block; none: no block; k_of_n: the straggler mask's
    count of blocks dropped at random."""
    if kind == "all":
        return jnp.ones((k,), bool)
    if kind == "none":
        return jnp.zeros((k,), bool)
    return jnp.ones((k,), bool).at[
        jax.random.permutation(key, k)[:_DROPPED[k]]].set(False)


# K = 20 leaves the last group of 16 overhanging K; n = 1100 and 700 leave
# the last row panel overhanging n.
@pytest.mark.parametrize("k,n,d,b,dtype,kind", [
    (13, 1100, 123, 128, jnp.float32, "k_of_n"),
    (13, 1100, 123, 128, jnp.bfloat16, "all"),
    (148, 1100, 130, 64, jnp.float32, "k_of_n"),
    (148, 700, 70, 64, jnp.bfloat16, "k_of_n"),
    (148, 700, 70, 64, jnp.float32, "none"),
    (20, 700, 130, 64, jnp.float32, "all"),
    (20, 700, 130, 64, jnp.bfloat16, "k_of_n"),
    (20, 1100, 70, 128, jnp.float32, "none"),
])
def test_count_sketch_live_mask_skips_dead_blocks(k, n, d, b, dtype, kind):
    """A dead block reads exactly 0; a live one is bit for bit what
    ``live=None`` gives, and ``live=None`` is the segment sum."""
    kh, ks, ka, kl = jax.random.split(jax.random.PRNGKey(k + n + d + b), 4)
    h = jax.random.randint(kh, (k, n), 0, b, dtype=jnp.int32)
    sigma = jax.random.rademacher(ks, (k, n), dtype=jnp.float32)
    a = jax.random.normal(ka, (n, d)).astype(dtype)
    live = _live_mask(kind, k, kl)
    every = np.asarray(count_sketch.count_sketch_apply(
        h, sigma, a, b, interpret=True))
    if kind == "all":
        live = live.astype(jnp.int32)       # an int mask works as well
    out = np.asarray(count_sketch.count_sketch_apply(
        h, sigma, a, b, live=live, interpret=True))
    on = np.asarray(live) != 0
    assert out.shape == (k, b, d) and out.dtype == np.float32
    np.testing.assert_array_equal(out[on], every[on])
    assert not out[~on].any()
    assert (~on).sum() == {"all": 0, "none": k,
                           "k_of_n": _DROPPED.get(k)}[kind]
    expect = ref.count_sketch_apply(h, sigma, a.astype(jnp.float32), b)
    np.testing.assert_allclose(every, np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


# The tiles at epsilon's and a9a's widths, at the distributed-average mode's
# b = 2048 > d, and at the widest block that fits: always within the VMEM
# budget, aligned to the (8, 128) rule.
@pytest.mark.parametrize("k,b,n,d,tiles", [
    (148, 256, 200_000, 2000, (16, 512, 512)),
    (13, 128, 32_000, 123, (16, 512, 128)),
    (19, 2048, 200_000, 2000, (8, 512, 128)),
    (8, 4096, 200_000, 2000, (8, 256, 128)),
    (2, 64, 300, 13, (8, 384, 128)),
])
def test_count_sketch_tiles_fit_the_vmem_budget(k, b, n, d, tiles):
    group, tn, td = count_sketch.pick_tiles(k, b, n, d)
    assert (group, tn, td) == tiles
    assert count_sketch.vmem_bytes(group, b, tn, td) \
        <= count_sketch.VMEM_BUDGET_BYTES
    assert group % 8 == 0 and tn % 128 == 0 and td % 128 == 0


def test_count_sketch_refuses_a_block_too_wide_for_vmem():
    with pytest.raises(ValueError, match="does not fit"):
        count_sketch.pick_tiles(8, 8192, 200_000, 2000)


def test_count_sketch_chip_script_checks_the_kernel():
    """benchmarks/count_sketch_chip.py at a small size on the CPU: the
    segment sums, the kernel at the picked tiles (all blocks live, then
    the straggler mask's 10 of 13) and at a given setting, each agreeing
    with the sums, and a setting over the budget skipped."""
    from benchmarks import count_sketch_chip
    rows = count_sketch_chip.measure(
        300, 130, 13, 64, count_sketch_chip.parse_tiles(
            "8/128/128,16/4096/4096"), 1, seed=2 ** 31 + 7)
    assert [r["variant"] for r in rows] == [
        "segment_sum", "mxu 16/384/256", "mxu 16/384/256 live 10/13",
        "mxu 8/128/128", "mxu 16/4096/4096"]
    assert [r.get("picked") for r in rows[1:4]] == [True, True, False]
    for r in rows[1:4]:
        assert r["rel_max"] <= 1e-5 and r["rel_fro"] <= 1e-5
        assert len(r["s"]) == 1 and r["ms_per_block"] > 0
    assert rows[2]["live_blocks"] == 10 and rows[2]["dead_max"] == 0.0
    assert "over the budget" in rows[4]["skipped"]
    assert count_sketch_chip.parse_cases("148x256,32x1024") == [
        (148, 256), (32, 1024)]




# ---------------------------------------------- the masked Gram, sketched_gram
# Tolerances per dtype: float32 sums in another order, and bfloat16's 8-bit
# significand (2^-8 = 3.9e-3 per rounding, a few roundings deep).
TOL = {jnp.float32: 1e-4, jnp.bfloat16: 2e-2}


def _mask(kind, k, key):
    """random: each block kept with p = 0.8, block 0 always; one_dropped:
    every block but block 1; single: block 2 alone; none: no block."""
    if kind == "random":
        return jax.random.bernoulli(key, 0.8, (k,)).at[0].set(True)
    if kind == "one_dropped":
        return jnp.ones((k,), bool).at[1].set(False)
    if kind == "single":
        return jnp.zeros((k,), bool).at[2].set(True)
    return jnp.zeros((k,), bool)


@pytest.mark.parametrize("k,b,d,dtype,kind", [
    (4, 64, 32, jnp.float32, "random"),
    (6, 128, 100, jnp.float32, "random"),     # ragged d
    (10, 256, 256, jnp.float32, "random"),
    (3, 65, 33, jnp.float32, "random"),       # ragged b and d
    (3, 64, 40, jnp.float32, "one_dropped"),
    (3, 64, 40, jnp.bfloat16, "one_dropped"),
    (3, 64, 16, jnp.float32, "none"),
])
def test_sketched_gram_matches_the_oracle(k, b, d, dtype, kind):
    """The solve's masked Gram against ``ref.oversketch_gram``; with no
    survivor the ``max(n_avail, 1)`` guard gives exact zeros."""
    key = jax.random.PRNGKey(k + b + d)
    a_t = (jax.random.normal(key, (k, b, d)) / 8.0).astype(dtype)
    surv = _mask(kind, k, jax.random.fold_in(key, 1))
    out = sketched_gram(a_t, surv)
    assert out.shape == (d, d)
    if kind == "none":
        assert not np.asarray(out).any()
        return
    expect = ref.oversketch_gram(a_t.astype(jnp.float32), surv)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect), rtol=TOL[dtype],
                               atol=TOL[dtype])


# ----------------------------------- each family's Gram, SketchFamily.gram
def _family_case(family, k, n, d, b, s, dtype, seed):
    """(family, its state, A, the oracle's Gram for a mask) with the
    state drawn here, so the oracle sees the same h, sigma and rows."""
    kh, ks, ka, kr = jax.random.split(jax.random.PRNGKey(seed), 4)
    # 1/sqrt(n) row scale keeps Gram entries O(1), so the tolerance is an
    # absolute figure.
    a = (jax.random.normal(ka, (n, d))
         / jnp.sqrt(jnp.asarray(n, jnp.float32))).astype(dtype)
    a32 = a.astype(jnp.float32)
    cfg = OverSketchConfig(b, b, 0.0)
    if family == "oversketch":
        h = jax.random.randint(kh, (k, n), 0, b, dtype=jnp.int32)
        sigma = jax.random.rademacher(ks, (k, n), dtype=jnp.float32)
        return (sketching.get(family, cfg),
                core_sketch.CountSketch(h=h, sigma=sigma, block_size=b), a,
                lambda m: ref.sketch_gram_count(h, sigma, a32, b, m))
    if family == "srht":
        sigma = jax.random.rademacher(ks, (k, n), dtype=jnp.float32)
        n_pad = 1 << max(0, (n - 1).bit_length())
        rows = jax.random.randint(kr, (k, b), 0, n_pad, dtype=jnp.int32)
        return (sketching.get(family, cfg), {"sigma": sigma, "rows": rows},
                a, lambda m: ref.sketch_gram_srht(rows, sigma, a32, m))
    h = jax.random.randint(kh, (k, s, n), 0, b, dtype=jnp.int32)
    sigma = jax.random.rademacher(ks, (k, s, n), dtype=jnp.float32)
    fam = sketching.SJLTFamily(cfg, nnz_per_row=s)
    return (fam, {"h": h, "sigma": sigma}, a,
            lambda m: ref.sketch_gram_sjlt(h, sigma, a32, b, m))


def _gram(fam, dtype):
    """``fam.gram``, jitted as the solve runs it.  A bfloat16 A goes
    through the OverSketch family unjitted: under jit ``apply_sketch``'s
    platform switch refuses it, as its kernel branch gives float32 and its
    segment-sum branch bfloat16 (a named debt in ROADMAP.md)."""
    if fam.name == "oversketch" and dtype == jnp.bfloat16:
        return fam.gram
    return jax.jit(fam.gram)


@pytest.mark.parametrize("family,k,n,d,b,s,kind,dtype", [
    ("oversketch", 2, 128, 32, 64, 1, "random", jnp.float32),
    ("oversketch", 4, 700, 37, 64, 1, "random", jnp.float32),
    ("oversketch", 3, 1000, 130, 128, 1, "random", jnp.float32),
    ("oversketch", 5, 520, 64, 256, 1, "random", jnp.float32),
    ("oversketch", 4, 300, 24, 64, 1, "single", jnp.float32),
    ("oversketch", 3, 200, 16, 64, 1, "none", jnp.float32),
    ("oversketch", 2, 256, 40, 64, 1, "one_dropped", jnp.float32),
    ("oversketch", 2, 256, 40, 64, 1, "one_dropped", jnp.bfloat16),
    ("oversketch", 3, 520, 200, 64, 1, "random", jnp.float32),
    ("srht", 2, 64, 20, 32, 1, "random", jnp.float32),
    ("srht", 3, 700, 37, 64, 1, "random", jnp.float32),
    ("srht", 2, 1024, 130, 128, 1, "random", jnp.float32),
    ("srht", 4, 300, 24, 64, 1, "single", jnp.float32),
    ("srht", 3, 200, 16, 64, 1, "none", jnp.float32),
    ("srht", 2, 256, 40, 64, 1, "one_dropped", jnp.float32),
    ("srht", 2, 256, 40, 64, 1, "one_dropped", jnp.bfloat16),
    ("srht", 3, 520, 200, 64, 1, "random", jnp.float32),
    ("sjlt", 2, 128, 32, 64, 1, "random", jnp.float32),
    ("sjlt", 3, 700, 37, 64, 4, "random", jnp.float32),
    ("sjlt", 2, 300, 130, 128, 8, "random", jnp.float32),
])
def test_family_gram_matches_the_oracle(family, k, n, d, b, s, kind, dtype):
    """``SketchFamily.gram`` (apply, then ``sketched_gram``) against the
    ``ref.sketch_gram_*`` oracle on the same draws: non-power-of-two n,
    ragged d, one survivor, none (the ``max(n_avail, 1)`` guard: zeros)."""
    fam, state, a, oracle = _family_case(family, k, n, d, b, s, dtype,
                                         seed=k * 7 + n + s)
    surv = _mask(kind, k, jax.random.PRNGKey(n + d))
    out = _gram(fam, dtype)(state, a, surv)
    assert out.shape == (d, d)
    if kind == "none":
        assert not np.asarray(out).any()
        return
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(jax.jit(oracle)(surv)),
                               rtol=TOL[dtype], atol=TOL[dtype])


# d = 64 to 4096 at b = 64: the widths the Hessian's Gram meets, the last
# past the kernel's 512-wide column tiles.
_SWEEP_N = {64: 300, 1536: 192, 4096: 128}


@pytest.mark.parametrize("d", [64, 1536, 4096])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("family", ["oversketch", "srht", "sjlt"])
def test_gram_differential_sweep(family, dtype, d):
    """Each family's Gram against its oracle, every block but the first
    surviving; for the OverSketch family also the TPU's form, the MXU
    kernel (interpreted) with the live mask, then ``sketched_gram``."""
    b, n = 64, _SWEEP_N[d]
    fam, state, a, oracle = _family_case(family, 3, n, d, b, 4, dtype,
                                         seed=d + 13 * (dtype == jnp.bfloat16))
    surv = jnp.ones((3,), bool).at[0].set(False)
    expect = np.asarray(jax.jit(oracle)(surv))
    grams = [_gram(fam, dtype)(state, a, surv)]
    if family == "oversketch":
        grams.append(sketched_gram(count_sketch.count_sketch_apply(
            state.h, state.sigma, a, b, live=surv, interpret=True), surv))
    for out in grams:
        assert out.shape == (d, d)
        np.testing.assert_allclose(np.asarray(out, np.float32), expect,
                                   rtol=TOL[dtype], atol=TOL[dtype])


def test_sjlt_s1_equals_count_sketch():
    """SJLT with one slot IS count-sketch: the two families' Grams agree on
    the same h, sigma and mask."""
    k, n, d, b = 2, 256, 40, 64
    fam_c, cs, a, _ = _family_case("oversketch", k, n, d, b, 1,
                                   jnp.float32, seed=5)
    fam_j = sketching.SJLTFamily(fam_c.cfg, nnz_per_row=1)
    surv = jnp.array([False, True])
    out_c = jax.jit(fam_c.gram)(cs, a, surv)
    out_j = jax.jit(fam_j.gram)(
        {"h": cs.h[:, None, :], "sigma": cs.sigma[:, None, :]}, a, surv)
    np.testing.assert_allclose(np.asarray(out_j), np.asarray(out_c),
                               rtol=1e-5, atol=1e-6)


# ------------------------------------------------- the default-path solve
def test_newton_default_path_matches_exact_newton():
    """The default path (count sketch, masked Gram, coded matvecs) ends
    within 1% of plain exact Newton after as many iterations."""
    from benchmarks.common import best_f
    from repro.core import (Dataset, LogisticRegression, NewtonConfig,
                            oversketched_newton)
    key = jax.random.PRNGKey(11)
    n, d = 600, 20
    kx, kw, ky = jax.random.split(key, 3)
    x = jax.random.uniform(kx, (n, d), minval=-1, maxval=1)
    wstar = jax.random.normal(kw, (d,))
    y = jnp.where(jax.random.uniform(ky, (n,)) <
                  jax.nn.sigmoid(x @ wstar), 1.0, -1.0)
    data = Dataset(x=x, y=y)
    obj = LogisticRegression(lam=1e-4)
    r_osn = oversketched_newton(
        obj, data, jnp.zeros(d),
        NewtonConfig(iters=4, sketch=OverSketchConfig(256, 64, 0.25),
                     coded_block_rows=64), model=None)
    r_ref = oversketched_newton(
        obj, data, jnp.zeros(d),
        NewtonConfig(iters=4, hessian_policy="exact"), model=None)
    assert r_osn.history["fval"][-1] <= best_f(r_ref.history, r_osn.history,
                                               rel=0.01)
