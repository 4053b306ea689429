"""2-D product code: the parity-only layout against the whole code,
encode/decode round trips, peeling under erasures, hypothesis property
sweep over random decodable patterns.

Every test runs on both operands the solve codes: X (the code of X's rows,
for X @ v) and XT (the code of X^T's rows, X's columns, for X^T @ v).  The
default shape is ragged for both: 500 rows are not a multiple of the
32-row blocks, and 300 columns fill 10 of the XT code's 16 slots."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import coded as cd

OPERANDS = ["X", "XT"]


def _setup(key, operand, rows=500, cols=300, block=32):
    """(m, v, code, parity, full code, exact product) for one operand."""
    m = jax.random.normal(key, (rows, cols))
    t = operand == "XT"
    v = jax.random.normal(jax.random.fold_in(key, 1), (rows if t else cols,))
    code = cd.make_code(cols if t else rows, block)
    parity = cd.encode_2d(m, code, transpose=t)
    full = cd.encode_full(m.T if t else m, code)
    exact = m.T @ v if t else m @ v
    return m, v, code, parity, full, exact


def _matvec(m, parity, v, code, operand, erased=None):
    return cd.coded_matvec(m, parity, v, code, erased,
                           transpose=operand == "XT")


@pytest.mark.parametrize("operand", OPERANDS)
def test_encode_shapes(operand):
    m, v, code, parity, full, _ = _setup(jax.random.PRNGKey(0), operand)
    g, b = code.grid, code.block_rows
    assert g >= 3
    assert full.shape == (g + 1, g + 1, b, v.shape[0])
    # Only the 2g+1 parity blocks are kept, in the operand's layout.
    if operand == "XT":
        assert parity.shape == (2 * g + 1, m.shape[0], b)
        parity = jnp.swapaxes(parity, 1, 2)
    else:
        assert parity.shape == (2 * g + 1, b, m.shape[1])
    for kept, whole in ((parity[:g], full[:g, g]), (parity[g:], full[g])):
        np.testing.assert_allclose(np.asarray(kept), np.asarray(whole),
                                   rtol=1e-5, atol=1e-4)
    # parity relations of the whole code
    np.testing.assert_allclose(np.asarray(full[:-1, -1]),
                               np.asarray(full[:-1, :-1].sum(axis=1)),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(full[-1]),
                               np.asarray(full[:-1].sum(axis=0)),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("operand", OPERANDS)
def test_parity_layout_products_equal_the_whole_codes(operand):
    m, v, code, parity, full, _ = _setup(jax.random.PRNGKey(7), operand)
    got = cd.block_products(m, parity, v, code, operand == "XT")
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(cd.coded_block_products(full, v)),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("operand", OPERANDS)
def test_no_erasure_roundtrip(operand):
    m, v, code, parity, _, exact = _setup(jax.random.PRNGKey(1), operand)
    y, ok = _matvec(m, parity, v, code, operand)
    assert bool(ok)
    np.testing.assert_allclose(np.asarray(y), np.asarray(exact),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("operand", OPERANDS)
def test_single_erasure_per_line_decodes(operand):
    m, v, code, parity, _, exact = _setup(jax.random.PRNGKey(2), operand)
    g = code.grid
    erased = jnp.zeros((g + 1, g + 1), bool)
    for i in range(g + 1):           # one erasure per row, distinct columns
        erased = erased.at[i, (i * 2) % (g + 1)].set(True)
    y, ok = _matvec(m, parity, v, code, operand, erased)
    assert bool(ok)
    np.testing.assert_allclose(np.asarray(y), np.asarray(exact),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("operand", OPERANDS)
def test_multi_round_peeling(operand):
    """A pattern needing >1 peel round (two erasures in a row, resolvable via
    columns first)."""
    m, v, code, parity, _, exact = _setup(jax.random.PRNGKey(3), operand)
    erased = jnp.zeros((code.grid + 1, code.grid + 1), bool)
    erased = erased.at[0, 0].set(True).at[0, 1].set(True)
    y, ok = _matvec(m, parity, v, code, operand, erased)
    assert bool(ok)
    np.testing.assert_allclose(np.asarray(y), np.asarray(exact),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("operand", OPERANDS)
def test_undecodable_pattern_flags_failure(operand):
    """A 2x2 erased square is a stopping set: decode must report failure."""
    m, v, code, parity, _, _ = _setup(jax.random.PRNGKey(4), operand)
    erased = jnp.zeros((code.grid + 1, code.grid + 1), bool)
    erased = erased.at[0, 0].set(True).at[0, 1].set(True)
    erased = erased.at[1, 0].set(True).at[1, 1].set(True)
    _, ok = _matvec(m, parity, v, code, operand, erased)
    assert not bool(ok)


@pytest.mark.parametrize("operand", OPERANDS)
def test_ragged_rows_padding(operand):
    """Neither dimension divisible by the block size."""
    m, v, code, parity, _, exact = _setup(jax.random.PRNGKey(5), operand,
                                          rows=409, cols=201, block=64)
    y, ok = _matvec(m, parity, v, code, operand)
    assert bool(ok)
    np.testing.assert_allclose(np.asarray(y), np.asarray(exact),
                               rtol=1e-4, atol=1e-4)


# Grids of 2x2, 3x3 and 5x5 workers, narrow and wide blocks, a width that is
# no multiple of 128, and a bfloat16 operand: one worker erased each time.
@pytest.mark.parametrize("rows,cols,block,dtype", [
    (64, 128, 64, jnp.float32),
    (64, 333, 32, jnp.float32),
    (1000, 512, 64, jnp.float32),
    (32, 200, 32, jnp.float32),
    (32, 200, 32, jnp.bfloat16),
])
@pytest.mark.parametrize("operand", OPERANDS)
def test_coded_matvec_block_shapes_and_dtypes(operand, rows, cols, block,
                                              dtype):
    """The solve's coded_matvec against X v (X^T v) computed in float32 from
    the same values."""
    key = jax.random.PRNGKey(rows + cols)
    m = (jax.random.normal(key, (rows, cols)) / 14.0).astype(dtype)
    t = operand == "XT"
    v = jax.random.normal(jax.random.fold_in(key, 1),
                          (rows if t else cols,)).astype(dtype)
    code = cd.make_code(cols if t else rows, block)
    parity = cd.encode_2d(m, code, transpose=t)
    g1 = code.grid + 1
    erased = jnp.zeros((g1, g1), bool).at[g1 - 1, 0].set(True)
    y, ok = _matvec(m, parity, v, code, operand, erased)
    m32, v32 = np.asarray(m, np.float32), np.asarray(v, np.float32)
    exact = m32.T @ v32 if t else m32 @ v32
    assert bool(ok) and y.shape == exact.shape
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(y, np.float32), exact, rtol=tol,
                               atol=tol)


@pytest.mark.parametrize("cell", [(1, 1), (2, 4), (4, 2), (4, 4)],
                         ids=["systematic", "row_parity", "col_parity",
                              "corner"])
@pytest.mark.parametrize("operand", OPERANDS)
def test_planted_corruption_is_caught_as_on_the_whole_code(operand, cell):
    """A corrupted cell of the parity layout's product grid is flagged and
    decoded exactly, as the same corruption of the whole code's is."""
    m, v, code, parity, full, exact = _setup(jax.random.PRNGKey(8),
                                             operand)
    assert code.grid == 4
    known = jnp.ones((code.grid + 1, code.grid + 1), bool)
    out_rows = exact.shape[0]
    for prods in (cd.block_products(m, parity, v, code, operand == "XT"),
                  cd.coded_block_products(full, v)):
        bad = prods.at[cell].add(7.5)
        assert bool(cd.detect_corrupted(bad, known, code)[cell])
        y, ok, flagged = cd.verified_decode(bad, known, code, out_rows)
        assert ok and flagged == 1
        np.testing.assert_allclose(np.asarray(y), np.asarray(exact),
                                   rtol=1e-4, atol=1e-4)


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), n_erase=st.integers(0, 5),
       operand=st.sampled_from(OPERANDS))
def test_random_erasures_property(seed, n_erase, operand):
    """Random erasure sets: if peeling reports success the answer is exact;
    erasing entire rows' worth (> 2g+1) is not generated here."""
    key = jax.random.PRNGKey(seed)
    m, v, code, parity, _, exact = _setup(key, operand, rows=300, cols=200,
                                          block=32)
    g1 = code.grid + 1
    idx = jax.random.choice(jax.random.fold_in(key, 2), g1 * g1,
                            (n_erase,), replace=False)
    erased = jnp.zeros((g1 * g1,), bool).at[idx].set(True).reshape(g1, g1)
    y, ok = _matvec(m, parity, v, code, operand, erased)
    if bool(ok):
        np.testing.assert_allclose(np.asarray(y), np.asarray(exact),
                                   rtol=1e-3, atol=1e-3)
    else:
        # failure must only happen when some line has >= 2 erasures
        row_counts = np.asarray(erased).sum(axis=1)
        col_counts = np.asarray(erased).sum(axis=0)
        assert (row_counts >= 2).any() and (col_counts >= 2).any()


@pytest.mark.parametrize("operand", OPERANDS)
def test_distributed_matches_local(operand):
    mesh = jax.make_mesh((1,), ("workers",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    m, v, code, parity, full, exact = _setup(jax.random.PRNGKey(6),
                                             operand, rows=256, cols=192,
                                             block=32)
    g1 = code.grid + 1
    w = code.num_workers
    erased = jnp.zeros((g1, g1), bool).at[1, 1].set(True)
    y_local, ok_local = _matvec(m, parity, v, code, operand, erased)
    enc_flat = full.reshape(w, code.block_rows, -1)
    y_dist, ok_dist = cd.distributed_coded_matvec(
        enc_flat, v, erased.reshape(-1), code, exact.shape[0], mesh=mesh,
        worker_axis="workers")
    assert bool(ok_local) and bool(ok_dist)
    np.testing.assert_allclose(np.asarray(y_local), np.asarray(y_dist),
                               rtol=1e-5, atol=1e-5)
