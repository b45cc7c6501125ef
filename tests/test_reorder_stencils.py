"""Reorder mapping (Eq. 3) and stencil-table invariants."""
import numpy as np
import pytest

from repro.core.reorder import _level_of_shape, flat_permutation, level_permutation
from repro.core.stencils import build_steps, interp_matrix


@pytest.mark.parametrize("shape", [(33,), (17, 33), (17, 17, 33), (49, 33, 17)])
def test_level_permutation_bijection(shape):
    perm, pos = level_permutation(shape, 16)
    n = int(np.prod(shape))
    anchors = n - perm.size
    assert anchors >= 1
    assert np.unique(perm).size == perm.size  # injective
    lev = _level_of_shape(shape, 16).reshape(-1)
    assert (lev[perm[0]] if perm.size else 4) == lev[perm].max()
    # level-descending order (paper: large strides first)
    levels_seq = lev[perm]
    assert (np.diff(levels_seq.astype(int)) <= 0).all()
    # inverse consistency
    assert np.array_equal(pos[perm], np.arange(perm.size))


def test_flat_permutation_sorted():
    perm = flat_permutation((33, 33), 16)
    assert (np.diff(perm) > 0).all()


@pytest.mark.parametrize("spline", ["linear", "cubic", "natural-cubic"])
@pytest.mark.parametrize("s", [8, 4, 2, 1])
def test_interp_matrix_partition_of_unity(spline, s):
    M, order = interp_matrix(17, s, spline)
    rows = np.arange(s, 17, 2 * s)
    assert np.allclose(M[rows].sum(axis=1), 1.0, atol=1e-6)  # reproduces constants
    assert (order[rows] >= 1).all()


@pytest.mark.parametrize("ndim", [1, 2, 3])
@pytest.mark.parametrize("scheme", ["md", "1d"])
@pytest.mark.parametrize("spline", ["linear", "cubic", "natural-cubic"])
def test_step_coverage(ndim, scheme, spline):
    steps = build_steps(ndim, 17, (8, 4, 2, 1), (spline,) * 4, (scheme,) * 4)
    cover = np.zeros((17,) * ndim, np.int32)
    for st in steps:
        cover += st.mask
        # weights only on masked points, summing to 1
        wsum = sum(np.asarray(w) for w in st.weights)
        assert np.allclose(wsum[st.mask], 1.0, atol=1e-6)
        assert np.allclose(wsum[~st.mask], 0.0)
    coords = np.meshgrid(*([np.arange(17)] * ndim), indexing="ij")
    anchors = np.ones((17,) * ndim, bool)
    for c in coords:
        anchors &= c % 16 == 0
    assert (cover[anchors] == 0).all()
    assert (cover[~anchors] == 1).all()


@pytest.mark.parametrize("scheme", ["1d-210", "1d-120", "1d-021"])
def test_sequential_ordering_coverage_and_distinct_masks(scheme):
    """Every sweep permutation still tiles each level exactly once, and a
    non-natural ordering really changes the per-step masks vs "1d"."""
    steps = build_steps(3, 17, (8, 4, 2, 1), ("cubic",) * 4, (scheme,) * 4)
    base = build_steps(3, 17, (8, 4, 2, 1), ("cubic",) * 4, ("1d",) * 4)
    cover = np.zeros((17,) * 3, np.int32)
    for st in steps:
        cover += st.mask
    coords = np.meshgrid(*([np.arange(17)] * 3), indexing="ij")
    anchors = np.ones((17,) * 3, bool)
    for c in coords:
        anchors &= c % 16 == 0
    assert (cover[anchors] == 0).all() and (cover[~anchors] == 1).all()
    assert any(not np.array_equal(a.mask, b.mask) for a, b in zip(steps, base))


def test_scheme_dims_validation():
    from repro.core.stencils import scheme_dims

    assert scheme_dims("md", 3) is None
    assert scheme_dims("1d", 3) == (0, 1, 2)
    assert scheme_dims("1d-210", 3) == (2, 1, 0)
    for bad in ("1d-21", "1d-0122", "1d-ab", "zigzag"):
        with pytest.raises(ValueError, match="scheme"):
            scheme_dims(bad, 3)


def test_exact_on_cubic_polynomial():
    """Cubic splines reproduce cubic polynomials away from block borders."""
    import jax.numpy as jnp

    from repro.core.predictor import compress_blocks, quant_steps

    t = np.linspace(-1, 1, 17).astype(np.float32)
    X, Y, Z = np.meshgrid(t, t, t, indexing="ij")
    poly = (X**3 + Y**3 - Z**3 + X * Y * Z).astype(np.float32)[None]
    steps = build_steps(3, 17, (8, 4, 2, 1), ("cubic",) * 4, ("md",) * 4)
    codes, outl, recon = compress_blocks(jnp.asarray(poly), *quant_steps(5e-4), steps, 16)
    # reconstruction within eb everywhere (quantization guarantees it)
    assert float(jnp.max(jnp.abs(recon - poly))) <= 1e-3 + 1e-6
