"""Adversarial-corpus sweep: bound-or-typed-error on every cell.

The contract (see :mod:`repro.testing.adversarial`): for every corpus
field and every spec, either the round-trip honors the declared bound —
bit-exactly on non-finite points, within eb on finite points — or
``compress`` raises a typed error (``ValueError`` family /
``BoundViolationError``). Silent corruption is the only forbidden
outcome. The tier-1 sweep runs the full grid under the chaos seed
(``REPRO_FAULTS`` replays a failing cell exactly); the tier-2 hypothesis
sweep feeds arbitrary float32 fields, NaN/Inf included.
"""
import numpy as np
import pytest

from repro.core import Compressor, CompressorSpec, max_abs_err
from repro.core.errors import BoundViolationError, SpecError
from repro.testing import CORPUS, corpus_field
from repro.testing.faults import fault_seed

# verify=full makes the contract airtight: every point is checked after
# encode, so a surviving container *proves* the bound and anything else
# must have raised
SPECS = [
    "lossy,abs,1e-2,verify=full",
    "lossy,rel,1e-3,verify=full",
    "lossy,pw_rel,1e-2,verify=full",
]

TYPED_ERRORS = (ValueError, SpecError, BoundViolationError)


def _bits(a: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(a, np.float32).view(np.uint32)


def _assert_bound(x: np.ndarray, y: np.ndarray, spec: CompressorSpec) -> None:
    assert y.shape == x.shape and y.dtype == np.float32
    fin = np.isfinite(x)
    # non-finite points restore bit-exactly (NaN payloads, Inf signs)
    assert np.array_equal(_bits(x[~fin]), _bits(y[~fin]))
    assert np.isfinite(y[fin]).all()
    if not fin.any():
        return
    xf = x[fin].astype(np.float64)
    yf = y[fin].astype(np.float64)
    tol = 2e-4  # the systemwide f32-rounding slack (1e-4) plus margin
    if spec.eb_mode == "abs":
        assert np.max(np.abs(xf - yf)) <= spec.eb * (1 + tol)
    elif spec.eb_mode == "rel":
        rng = float(np.max(xf)) - float(np.min(xf))
        assert np.max(np.abs(xf - yf)) <= spec.eb * rng * (1 + tol) + 1e-30
    else:  # pw_rel: per-point, zeros exact
        zero = xf == 0.0
        assert np.array_equal(_bits(x[fin][zero]), _bits(y[fin][zero]))
        nz = ~zero
        if nz.any():
            assert np.max(np.abs(xf[nz] - yf[nz]) / np.abs(xf[nz])) <= spec.eb * (1 + tol)


@pytest.mark.parametrize("spec_str", SPECS)
@pytest.mark.parametrize("name", sorted(CORPUS))
def test_bound_or_typed_error(name, spec_str):
    x = corpus_field(name, seed=fault_seed())
    spec = CompressorSpec.from_string(spec_str)
    comp = Compressor(spec)
    try:
        buf = comp.compress(x)
    except TYPED_ERRORS:
        return  # typed refusal is a legal outcome; silence is not
    _assert_bound(x, comp.decompress(buf), spec)


@pytest.mark.parametrize("name", sorted(CORPUS))
def test_default_verify_sample_contract(name):
    """The default spec (verify=sample) satisfies the same contract on the
    corpus: the non-finite canonicalization is exact by construction and
    the deterministic sample covers these field sizes entirely."""
    x = corpus_field(name, seed=fault_seed())
    spec = CompressorSpec(eb=1e-3)
    comp = Compressor(spec)
    try:
        buf = comp.compress(x)
    except TYPED_ERRORS:
        return
    tel = comp.last_telemetry or {}
    if np.isfinite(x).any() and not np.isfinite(x).all():
        assert tel.get("nonfinite", {}).get("n", 0) > 0
    _assert_bound(x, comp.decompress(buf), spec)


def test_all_nonfinite_short_circuits():
    x = corpus_field("all_nan")
    comp = Compressor(CompressorSpec(eb=1e-3))
    buf = comp.compress(x)
    assert len(buf) < 1024  # trivial container, no predictor ran
    y = comp.decompress(buf)
    assert np.array_equal(_bits(x), _bits(y).reshape(x.shape))


def test_finite_containers_unchanged_by_verify():
    """verify costs zero bytes: a finite field encodes to the identical
    container whether verification runs or not."""
    x = corpus_field("single_voxel_outlier")
    b_off = Compressor(CompressorSpec(eb=1e-3, verify="off")).compress(x)
    b_on = Compressor(CompressorSpec(eb=1e-3, verify="full")).compress(x)
    assert b_off == b_on


def test_sweep_is_seed_deterministic():
    a = corpus_field("scattered_nonfinite", seed=123)
    b = corpus_field("scattered_nonfinite", seed=123)
    assert np.array_equal(_bits(a), _bits(b))


def test_near_f32_max_field_decodes_finite_within_bound():
    """The property below once found this: predictions of a field near the
    f32 limit overflow to inf - inf = NaN, which a ``> bound`` outlier test
    and a ``> limit`` verify check both let through."""
    x = np.full((2, 10), 2.9445248e38, np.float32)
    x[0, 0] = 0.0
    spec = CompressorSpec(eb=0.1, eb_mode="rel", autotune=False, verify="full")
    comp = Compressor(spec)
    _assert_bound(x, comp.decompress(comp.compress(x)), spec)


# --------------------------------------------------------------- tier 2
@pytest.mark.tier2
def test_hypothesis_bound_or_typed_error():
    hypothesis = pytest.importorskip("hypothesis", reason="optional dev dependency")
    from hypothesis import given, settings
    from hypothesis import strategies as st
    from hypothesis.extra import numpy as hnp

    @given(
        data=hnp.arrays(
            np.float32,
            hnp.array_shapes(min_dims=1, max_dims=3, min_side=1, max_side=24),
            elements=st.floats(width=32, allow_nan=True, allow_infinity=True),
        ),
        eb=st.sampled_from([1e-1, 1e-3]),
        mode=st.sampled_from(["abs", "rel", "pw_rel"]),
    )
    @settings(max_examples=40, deadline=None)
    def prop(data, eb, mode):
        spec = CompressorSpec(eb=eb, eb_mode=mode, autotune=False, verify="full")
        comp = Compressor(spec)
        try:
            buf = comp.compress(data)
        except TYPED_ERRORS:
            return
        _assert_bound(data, comp.decompress(buf), spec)

    prop()


@pytest.mark.tier2
def test_property_max_abs_err_ignores_nonfinite():
    x = corpus_field("nan_slab")
    y = np.where(np.isfinite(x), x, 0.0).astype(np.float32)
    assert np.isfinite(max_abs_err(x, y))
