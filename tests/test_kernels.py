"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis.

All kernels run in interpret mode on CPU (the TPU-target validation path)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.sketch import OverSketchConfig
from repro.kernels import count_sketch, ops, ref


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
    out = ops.count_sketch_apply(h, sigma, a, b)
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
    out = ops.count_sketch_apply(h, sigma, a, b)
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
    out = ops.count_sketch_apply(h, sigma, a, b)
    expect = ref.count_sketch_apply(h, sigma, a, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-4, atol=1e-4)


# The MXU kernel's grid at the shapes it has to handle: K not a multiple of
# the block group (13, 148), n not a multiple of the row panel, ragged d,
# and each block size the configurations use.
@pytest.mark.parametrize("k,n,d,b", [
    (13, 1100, 123, 128),
    (13, 300, 130, 256),
    (148, 1100, 130, 64),
    (148, 2500, 123, 256),
    (148, 1100, 123, 128),
    (13, 2500, 130, 64),
])
def test_count_sketch_grid_matches_segment_sum(k, n, d, b):
    kh, ks, ka = jax.random.split(jax.random.PRNGKey(k + n + d + b), 3)
    h = jax.random.randint(kh, (k, n), 0, b, dtype=jnp.int32)
    sigma = jax.random.rademacher(ks, (k, n), dtype=jnp.float32)
    a = jax.random.normal(ka, (n, d))
    out = ops.count_sketch_apply(h, sigma, a, b, interpret=True)
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

    assert rel(ops.count_sketch_apply(h, sigma, a, b, interpret=True)) <= 1e-6
    one_pass = ops.count_sketch_apply(h, sigma, a.astype(jnp.bfloat16), b,
                                      interpret=True)
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


# ------------------------------------------------------------ oversketch gram
@pytest.mark.parametrize("k,b,d", [
    (4, 64, 32),
    (6, 128, 100),   # ragged d
    (10, 256, 256),
    (3, 65, 33),     # ragged b and d
])
def test_oversketch_gram_shapes(k, b, d):
    key = jax.random.PRNGKey(k + b + d)
    a_t = jax.random.normal(key, (k, b, d))
    surv = jax.random.bernoulli(jax.random.fold_in(key, 1), 0.8, (k,))
    surv = surv.at[0].set(True)   # at least one survivor
    out = ops.oversketch_gram(a_t, surv)
    expect = ref.oversketch_gram(a_t, surv)
    assert out.shape == (d, d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-4, atol=1e-4)


def test_oversketch_gram_all_masked_is_safe():
    a_t = jnp.ones((3, 64, 16))
    out = ops.oversketch_gram(a_t, jnp.zeros((3,), bool))
    assert np.isfinite(np.asarray(out)).all()


# ------------------------------------------------------------- coded matvec
@pytest.mark.parametrize("w,b,s", [
    (4, 64, 128),
    (9, 32, 333),    # ragged s
    (25, 64, 512),
])
def test_coded_matvec_shapes(w, b, s):
    key = jax.random.PRNGKey(w + s)
    enc = jax.random.normal(key, (w, b, s))
    x = jax.random.normal(jax.random.fold_in(key, 1), (s,))
    erased = jax.random.bernoulli(jax.random.fold_in(key, 2), 0.2, (w,))
    out = ops.coded_block_matvec(enc, x, erased)
    expect = ref.coded_block_matvec(enc, x, erased)
    assert out.shape == (w, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-4, atol=1e-4)


# --------------------------------------------------- fused sketch->gram
def _sketch_inputs(seed, k, n, d, b, n_pad_srht=None):
    key = jax.random.PRNGKey(seed)
    kh, ks, ka, kr, km = jax.random.split(key, 5)
    h = jax.random.randint(kh, (k, n), 0, b, dtype=jnp.int32)
    sigma = jax.random.rademacher(ks, (k, n), dtype=jnp.float32)
    # 1/sqrt(n) row scale keeps Gram entries O(1) so the <= 1e-4 max-abs
    # acceptance bound is an absolute float32 figure, not a moving target.
    a = jax.random.normal(ka, (n, d)) / jnp.sqrt(jnp.asarray(n, jnp.float32))
    n_pad = n_pad_srht or (1 << max(0, (n - 1).bit_length()))
    rows = jax.random.randint(kr, (k, b), 0, n_pad, dtype=jnp.int32)
    surv = jax.random.bernoulli(km, 0.6, (k,)).at[0].set(True)
    return h, sigma, a, rows, surv


@pytest.mark.parametrize("k,n,d,b", [
    (2, 128, 32, 64),
    (4, 700, 37, 64),      # non-power-of-two n, ragged d
    (3, 1000, 130, 128),   # d % 128 != 0 on both sides of a tile
    (5, 520, 64, 256),     # n % tile_n != 0
])
def test_sketch_gram_count_fused_matches_unfused(k, n, d, b):
    h, sigma, a, _, surv = _sketch_inputs(k * 7 + n, k, n, d, b)
    out = ops.sketch_gram_count(h, sigma, a, b, surv)
    expect = ref.sketch_gram_count(h, sigma, a, b, surv)
    assert out.shape == (d, d)
    assert float(jnp.abs(out - expect).max()) <= 1e-4


@pytest.mark.parametrize("k,n,d,b", [
    (2, 64, 20, 32),
    (3, 700, 37, 64),      # non-power-of-two n (pads to 1024 internally)
    (2, 1024, 130, 128),   # ragged d
])
def test_sketch_gram_srht_fused_matches_unfused(k, n, d, b):
    _, sigma, a, rows, surv = _sketch_inputs(k * 11 + n, k, n, d, b)
    out = ops.sketch_gram_srht(rows, sigma, a, surv)
    expect = ref.sketch_gram_srht(rows, sigma, a, surv)
    assert out.shape == (d, d)
    assert float(jnp.abs(out - expect).max()) <= 1e-4


def test_sketch_gram_single_survivor():
    k, n, d, b = 4, 300, 24, 64
    h, sigma, a, rows, _ = _sketch_inputs(0, k, n, d, b)
    surv = jnp.zeros((k,), bool).at[2].set(True)
    for out, expect in [
        (ops.sketch_gram_count(h, sigma, a, b, surv),
         ref.sketch_gram_count(h, sigma, a, b, surv)),
        (ops.sketch_gram_srht(rows, sigma, a, surv),
         ref.sketch_gram_srht(rows, sigma, a, surv)),
    ]:
        assert float(jnp.abs(out - expect).max()) <= 1e-4


def test_sketch_gram_all_masked_is_safe():
    k, n, d, b = 3, 200, 16, 64
    h, sigma, a, rows, _ = _sketch_inputs(1, k, n, d, b)
    surv = jnp.zeros((k,), bool)
    assert np.isfinite(
        np.asarray(ops.sketch_gram_count(h, sigma, a, b, surv))).all()
    assert np.isfinite(
        np.asarray(ops.sketch_gram_srht(rows, sigma, a, surv))).all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sketch_gram_dtypes(dtype):
    k, n, d, b = 2, 256, 40, 64
    h, sigma, a, rows, surv = _sketch_inputs(2, k, n, d, b)
    a = a.astype(dtype)
    # Both kernels accumulate in float32, so after the (exact) bf16->f32
    # cast they must match the f32 oracle on the same cast values.
    a32 = a.astype(jnp.float32)
    out_c = ops.sketch_gram_count(h, sigma, a, b, surv)
    np.testing.assert_allclose(
        np.asarray(out_c), np.asarray(ref.sketch_gram_count(h, sigma, a32,
                                                            b, surv)),
        rtol=1e-4, atol=1e-4)
    out_s = ops.sketch_gram_srht(rows, sigma, a, surv)
    np.testing.assert_allclose(
        np.asarray(out_s), np.asarray(ref.sketch_gram_srht(rows, sigma,
                                                           a32, surv)),
        rtol=1e-4, atol=1e-4)


# ------------------------------- fused-vs-unfused differential sweep (tiled)
# d = 64 fits one resident output tile; 1536 and 4096 are past the old
# single-tile VMEM budget, where pre-tiling code silently fell back to the
# unfused pair — the path/pick assertions pin that the d-tiled fused grid
# actually runs there now.
_SWEEP_N = {64: 300, 1536: 192, 4096: 128}


@pytest.mark.parametrize("d", [64, 1536, 4096])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("family", ["oversketch", "srht", "sjlt"])
def test_fused_differential_sweep(family, dtype, d):
    from repro import sketching
    from repro.core.sketch import OverSketchConfig, sketched_gram

    b = 64
    n = _SWEEP_N[d]
    fam = sketching.get(family, OverSketchConfig(128, b, 0.25))
    d_pad = d + ((-d) % 128)
    nnz = getattr(fam, "nnz_per_row", 1)
    expect_path = "fused" if d <= 1024 else "fused_tiled"
    assert fam.fused_path(d) == expect_path
    assert ops.fused_path(b, d, nnz=nnz) == expect_path
    if expect_path == "fused_tiled":
        assert ops.pick_d_tile(b, d, nnz=nnz) < d_pad

    key = jax.random.PRNGKey(d + 13 * (dtype == jnp.bfloat16))
    state = fam.sample(key, n)
    a = jax.random.normal(jax.random.fold_in(key, 1), (n, d))
    a = (a / jnp.sqrt(jnp.asarray(n, jnp.float32))).astype(dtype)
    surv = jnp.ones((fam.cfg.total_blocks,), bool).at[0].set(False)
    fused = fam.gram_fused(state, a, surv)
    assert fused is not None           # the decline path is gone for any d
    # The kernel casts to f32 up front; the unfused oracle runs on the
    # exactly-cast values so <= 1e-4 is an absolute f32 agreement bound.
    a32 = a.astype(jnp.float32)
    expect = sketched_gram(fam.apply(state, a32), surv)
    assert fused.shape == (d, d)
    assert float(jnp.abs(fused - expect).max()) <= 1e-4


def test_fused_runs_to_d8192():
    """Acceptance bound: power-of-two-padded d up to 8192 takes the tiled
    fused grid (never None, never the unfused pair) and agrees."""
    k, n, d, b = 1, 64, 8192, 64
    h, sigma, a, _, _ = _sketch_inputs(3, k, n, d, b)
    surv = jnp.ones((k,), bool)
    assert ops.fused_path(b, d) == "fused_tiled"
    out = ops.sketch_gram_count(h, sigma, a, b, surv)
    expect = ref.sketch_gram_count(h, sigma, a, b, surv)
    assert float(jnp.abs(out - expect).max()) <= 1e-4


def test_sketch_gram_forced_tiny_tile_matches():
    """Forcing d_tile below d exercises the multi-tile grid on shapes the
    default pick would run single-tile — diag/off-diag fold coverage."""
    k, n, d, b = 3, 520, 200, 64
    h, sigma, a, rows, surv = _sketch_inputs(4, k, n, d, b)
    out = ops.sketch_gram_count(h, sigma, a, b, surv, d_tile=128)
    assert float(jnp.abs(out - ref.sketch_gram_count(h, sigma, a, b,
                                                     surv)).max()) <= 1e-4
    out_s = ops.sketch_gram_srht(rows, sigma, a, surv, d_tile=128)
    assert float(jnp.abs(out_s - ref.sketch_gram_srht(rows, sigma, a,
                                                      surv)).max()) <= 1e-4


# --------------------------------------------------- fused sjlt entry point
@pytest.mark.parametrize("k,s,n,d,b", [
    (2, 1, 128, 32, 64),    # s=1 degenerates to count-sketch
    (3, 4, 700, 37, 64),    # non-power-of-two n, ragged d
    (2, 8, 300, 130, 128),  # deep slot axis, d crossing a lane tile
])
def test_sketch_gram_sjlt_fused_matches_unfused(k, s, n, d, b):
    key = jax.random.PRNGKey(k * 3 + s + n)
    kh, ks, ka, km = jax.random.split(key, 4)
    h = jax.random.randint(kh, (k, s, n), 0, b, dtype=jnp.int32)
    sigma = jax.random.rademacher(ks, (k, s, n), dtype=jnp.float32)
    a = jax.random.normal(ka, (n, d)) / jnp.sqrt(jnp.asarray(n, jnp.float32))
    surv = jax.random.bernoulli(km, 0.6, (k,)).at[0].set(True)
    out = ops.sketch_gram_sjlt(h, sigma, a, b, surv)
    expect = ref.sketch_gram_sjlt(h, sigma, a, b, surv)
    assert out.shape == (d, d)
    assert float(jnp.abs(out - expect).max()) <= 1e-4


def test_sjlt_s1_equals_count_sketch():
    """SJLT with one slot IS count-sketch: both fused entry points agree."""
    k, n, d, b = 2, 256, 40, 64
    h, sigma, a, _, surv = _sketch_inputs(5, k, n, d, b)
    out_c = ops.sketch_gram_count(h, sigma, a, b, surv)
    out_j = ops.sketch_gram_sjlt(h[:, None, :], sigma[:, None, :], a, b, surv)
    np.testing.assert_allclose(np.asarray(out_j), np.asarray(out_c),
                               rtol=1e-5, atol=1e-6)


# ------------------------------------------------------------ two-pass fwht
@pytest.mark.parametrize("k,n,d", [
    (2, 64, 20),       # tiny d (pads to one 128 lane tile)
    (1, 1024, 130),    # d % tile_d != 0
    (2, 2048, 17),
    (1, 4096, 256),
])
def test_fwht_two_pass_matches_butterfly_oracle(k, n, d):
    x = jax.random.normal(jax.random.PRNGKey(n + d), (k, n, d))
    np.testing.assert_allclose(np.asarray(ops.fwht_two_pass(x)),
                               np.asarray(ref.fwht(x)),
                               rtol=1e-4, atol=1e-4)


def test_fwht_two_pass_rejects_non_pow2():
    with pytest.raises(ValueError, match="power of two"):
        ops.fwht_two_pass(jnp.zeros((1, 100, 4)))


def test_fwht_dispatches_two_pass_beyond_panel_budget():
    """An n whose monolithic (n, td) panel exceeds the documented VMEM
    budget must still go through ops.fwht (via the two-pass kernel) and
    match the oracle."""
    from repro.kernels.srht import MAX_PANEL_BYTES, panel_vmem_bytes
    n = 32768
    assert panel_vmem_bytes(n, d=8) > MAX_PANEL_BYTES
    x = jax.random.normal(jax.random.PRNGKey(5), (1, n, 8))
    np.testing.assert_allclose(np.asarray(ops.fwht(x)),
                               np.asarray(ref.fwht(x)),
                               rtol=1e-4, atol=1e-4)


def test_fwht_two_pass_is_involution():
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 512, 64))
    y = ops.fwht_two_pass(ops.fwht_two_pass(x))
    np.testing.assert_allclose(np.asarray(y), np.asarray(x),
                               rtol=1e-4, atol=1e-4)


# --------------------------------------- dtype sweep, remaining entry points
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fwht_dtypes_both_paths(dtype):
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 256, 40)).astype(dtype)
    expect = ref.fwht(x.astype(jnp.float32))
    for out in (ops.fwht(x), ops.fwht_two_pass(x)):
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_oversketch_gram_dtypes(dtype):
    key = jax.random.PRNGKey(9)
    a_t = (jax.random.normal(key, (3, 64, 40)) / 8.0).astype(dtype)
    surv = jnp.ones((3,), bool).at[1].set(False)
    out = ops.oversketch_gram(a_t, surv)
    expect = ref.oversketch_gram(a_t.astype(jnp.float32), surv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_coded_matvec_dtypes(dtype):
    key = jax.random.PRNGKey(10)
    enc = (jax.random.normal(key, (4, 32, 200)) / 14.0).astype(dtype)
    x = jax.random.normal(jax.random.fold_in(key, 1), (200,)).astype(dtype)
    erased = jnp.zeros((4,), bool).at[2].set(True)
    out = ops.coded_block_matvec(enc, x, erased)
    expect = ref.coded_block_matvec(enc.astype(jnp.float32),
                                    x.astype(jnp.float32), erased)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-4, atol=1e-4)


# ------------------------------------------- end-to-end kernels inside newton
def test_newton_with_kernels_matches_reference_path():
    from repro.core import (Dataset, LogisticRegression, NewtonConfig,
                            OverSketchConfig, oversketched_newton)
    key = jax.random.PRNGKey(11)
    n, d = 600, 20
    kx, kw, ky = jax.random.split(key, 3)
    x = jax.random.uniform(kx, (n, d), minval=-1, maxval=1)
    wstar = jax.random.normal(kw, (d,))
    y = jnp.where(jax.random.uniform(ky, (n,)) <
                  jax.nn.sigmoid(x @ wstar), 1.0, -1.0)
    data = Dataset(x=x, y=y)
    obj = LogisticRegression(lam=1e-4)
    base = dict(iters=4, sketch=OverSketchConfig(256, 64, 0.25),
                coded_block_rows=64)
    r_ref = oversketched_newton(obj, data, jnp.zeros(d),
                                NewtonConfig(**base), model=None)
    r_ker = oversketched_newton(obj, data, jnp.zeros(d),
                                NewtonConfig(use_kernels=True, **base),
                                model=None)
    # Same sketch seed => identical Hessians => identical trajectories.
    np.testing.assert_allclose(np.asarray(r_ref.w), np.asarray(r_ker.w),
                               rtol=1e-4, atol=1e-5)
