"""Property-based invariants for the sketching subsystem (hypothesis-optional).

Three families of properties, all via the ``_hypothesis_compat`` shim so
tier-1 collection never requires hypothesis (the tests skip cleanly when
it is absent, and CI runs them in a dedicated job with it installed):

1. **Per-block Gram unbiasedness** (paper Lemma 6.1 / base.py contract):
   ``E[A^T S S^T A] = A^T A`` for every registered family — checked by
   Monte-Carlo averaging the survivor-rescaled Gram estimate over fresh
   sketch draws, against a tolerance a few sigma above the estimator's
   MC error ("Newton Meets Marchenko-Pastur" says correctness must hold
   across wide m/d regimes, so shapes are drawn, not fixed).
2. **k-of-n survivor-mask invariance** (OverSketch Eq. 4 semantics):
   dropping blocks + rescaling is EXACT — the masked estimator equals
   the plain average over the surviving subset, for any mask including
   the single-survivor edge.
3. **Gram agreement across padding edges**: each encode family's
   ``gram`` matches its ``kernels/ref.py`` oracle to 1e-4 with n and d
   drawn across the 128-row and 128-lane edges and one survivor or many.

Families are looped inside the test bodies (not pytest.parametrize): the
hypothesis-compat shim replaces @given tests with zero-arg skippers, so
externally injected params would break hypothesis-less collection.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from _hypothesis_compat import given, settings, st

from repro import sketching
from repro.core.sketch import CountSketch, OverSketchConfig, sketch_impl
from repro.kernels import ref

FAMILIES = ["oversketch", "srht", "sjlt", "gaussian", "nystrom", "leverage"]
_CFG = OverSketchConfig(sketch_dim=64, block_size=16,
                        straggler_tolerance=0.25)   # 4 + 1 blocks


def _data(seed, n, d):
    a = jax.random.normal(jax.random.PRNGKey(seed ^ 0x5EED), (n, d))
    return a / jnp.sqrt(jnp.asarray(n, jnp.float32))


# ------------------------------------------------- per-block unbiasedness
@settings(max_examples=3, deadline=None)
@given(seed=st.integers(0, 2**16),
       n=st.sampled_from([24, 33, 40]),      # 33: not a multiple of anything
       d=st.sampled_from([5, 8]))
def test_gram_unbiased(seed, n, d):
    """MC mean of the rescaled masked Gram converges to A^T A, for every
    registered family."""
    a = _data(seed, n, d)
    target = a.T @ a
    draws = 32
    key = jax.random.PRNGKey(seed)
    for family in FAMILIES:
        fam = sketching.get(family, _CFG)
        grams = [fam.gram(fam.sample(jax.random.fold_in(key, i), n), a, None)
                 for i in range(draws)]
        mean = jnp.mean(jnp.stack(grams), axis=0)
        rel = float(jnp.linalg.norm(mean - target) / jnp.linalg.norm(target))
        # MC error of the mean over draws * total_blocks block-grams is
        # ~ sqrt(d/b / (draws*blocks)) ~ 0.04-0.06 here; 0.3 is > 4 sigma.
        assert rel < 0.3, f"{family}: relative bias {rel:.3f}"


# ------------------------------------------- k-of-n survivor invariance
@settings(max_examples=5, deadline=None)
@given(seed=st.integers(0, 2**16),
       n=st.sampled_from([40, 100, 129]),
       d=st.sampled_from([7, 17]),
       single=st.booleans(),
       idx=st.integers(0, 4))
def test_survivor_mask_invariance(seed, n, d, single, idx):
    """Masked + rescaled == plain average over the surviving subset; the
    straggler-drop rescale is exact for every family and any mask, down
    to a single survivor."""
    blocks = _CFG.total_blocks
    idx = idx % blocks
    a = _data(seed + 1, n, d)
    if single:
        mask = jnp.zeros((blocks,), bool).at[idx].set(True)
    else:
        mask = jax.random.bernoulli(jax.random.PRNGKey(seed + 2), 0.5,
                                    (blocks,)).at[idx].set(True)
    for family in FAMILIES:
        fam = sketching.get(family, _CFG)
        state = fam.sample(jax.random.PRNGKey(seed), n)
        got = fam.gram(state, a, mask)
        a_t = fam.apply(state, a)
        kept = a_t[np.asarray(mask)]
        expect = jnp.einsum("kbd,kbe->de", kept, kept) / kept.shape[0]
        np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                                   rtol=2e-5, atol=2e-5,
                                   err_msg=f"family={family}")


# ------------------------------------------- Gram across padding edges
@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 2**20),
       n=st.integers(50, 300),               # crosses 128-row panels
       d=st.integers(3, 150),                # crosses the 128-lane tile
       single=st.booleans())
def test_gram_padding_edges(seed, n, d, single):
    """``family.gram`` of the oversketch, srht and sjlt families vs the
    ``ref.sketch_gram_*`` oracle on the same draws, with shapes
    straddling every padding edge."""
    k, b, s = 2, 32, 3
    cfg = OverSketchConfig(b, b, 0.0)
    key = jax.random.PRNGKey(seed)
    kh, ks, ka, kr, km = jax.random.split(key, 5)
    a = jax.random.normal(ka, (n, d)) / jnp.sqrt(jnp.asarray(n, jnp.float32))
    if single:
        surv = jnp.zeros((k,), bool).at[1].set(True)
    else:
        surv = jax.random.bernoulli(km, 0.6, (k,)).at[0].set(True)
    h = jax.random.randint(kh, (k, n), 0, b, dtype=jnp.int32)
    sg = jax.random.rademacher(ks, (k, n), dtype=jnp.float32)
    n_pad = 1 << max(0, (n - 1).bit_length())
    rows = jax.random.randint(kr, (k, b), 0, n_pad, dtype=jnp.int32)
    hj = jax.random.randint(kh, (k, s, n), 0, b, dtype=jnp.int32)
    sj = jax.random.rademacher(jax.random.fold_in(ks, 1), (k, s, n),
                               dtype=jnp.float32)
    oracle = functools.partial(jax.jit, static_argnames="block_size")
    cells = [
        ("count", sketching.get("oversketch", cfg),
         CountSketch(h=h, sigma=sg, block_size=b),
         oracle(ref.sketch_gram_count)(h, sg, a, block_size=b,
                                       survivors=surv)),
        ("srht", sketching.get("srht", cfg), {"sigma": sg, "rows": rows},
         jax.jit(ref.sketch_gram_srht)(rows, sg, a, surv)),
        ("sjlt", sketching.SJLTFamily(cfg, nnz_per_row=s),
         {"h": hj, "sigma": sj},
         oracle(ref.sketch_gram_sjlt)(hj, sj, a, block_size=b,
                                      survivors=surv)),
    ]
    for mode, fam, state, expect in cells:
        out = jax.jit(fam.gram)(state, a, surv)
        assert out.shape == (d, d)
        err = float(jnp.abs(out - expect).max())
        assert err <= 1e-4, f"mode={mode}: max_err={err:.2e}"


# ------------------------------------------------------- plain (no-shim)
def test_all_six_families_registered():
    """The property sweep above covers exactly the registered set."""
    assert sorted(FAMILIES) == sketching.available()


def test_apply_path_reporting_consistent():
    """apply_path across the registry: the OverSketch family names the
    implementation ``apply_sketch`` picks for the platform and block
    size, every other family its one jnp form."""
    for name in sketching.available():
        fam = sketching.get(name, _CFG)
        for platform in ("tpu", "cpu"):
            path = fam.apply_path(platform)
            if name == "oversketch":
                assert path == sketch_impl(platform, _CFG.block_size)
            else:
                assert path == "unfused"
    assert sketching.get("oversketch", _CFG).apply_path("tpu") \
        == "mxu_count_sketch"
