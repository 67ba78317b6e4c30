"""``fluid.fdiv``: float32 division rounded to nearest on every backend.

The TPU divides as a refined reciprocal times the dividend, 1-2 ulps
off on about a third of quotients; ``round_quotient`` corrects such a
quotient with one exact residual.  On the CPU the correction is not
taken and ``fdiv`` is plain division, so CPU results are unchanged."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fluid import fdiv, round_quotient


def _pairs(n=1 << 16, seed=0):
    """Random float32 pairs over the step's magnitudes (1e-9 .. 1e11),
    the first 512 equal (an exact quotient of 1)."""
    rng = np.random.default_rng(seed)
    x, y = ((rng.uniform(1, 2, n) * 10.0 ** rng.integers(-9, 11, n)).astype(np.float32)
            for _ in range(2))
    x[:512] = y[:512]
    return x, y


def _ulps(q, k):
    """``q`` moved by ``k`` ulps."""
    toward = np.float32(np.inf if k > 0 else -np.inf)
    for _ in range(abs(k)):
        q = np.nextafter(q, toward)
    return q


@pytest.mark.parametrize("k", [-2, -1, 0, 1, 2])
def test_round_quotient_corrects_a_nearby_quotient(k):
    x, y = _pairs(seed=k + 2)
    want = x / y
    got = np.asarray(jax.jit(round_quotient)(_ulps(want, k), x, y))
    assert np.array_equal(got, want), int((got != want).sum())
    assert (got[:512] == 1.0).all()


def test_round_quotient_keeps_a_nonfinite_correction():
    x = np.array([1.0, 0.0, 3.0, np.inf], np.float32)
    y = np.array([0.0, 0.0, np.inf, 2.0], np.float32)
    with np.errstate(divide="ignore", invalid="ignore"):
        q = x / y
    got = np.asarray(jax.jit(round_quotient)(q, x, y))
    assert np.array_equal(got, q, equal_nan=True)


@pytest.mark.parametrize("y_kind", ["array", "scalar"])
def test_fdiv_is_plain_division_off_the_tpu(y_kind):
    x, y = _pairs(seed=7)
    y = y if y_kind == "array" else np.float32(1e-6)
    got = np.asarray(jax.jit(fdiv)(x, y))
    assert got.dtype == np.float32
    assert np.array_equal(got, x / y)


@pytest.mark.parametrize("platform,corrected", [("tpu", True), ("cpu", False)])
def test_fdiv_lowers_the_correction_for_the_tpu_only(platform, corrected):
    """The TPU lowering carries the exact-residual correction (the split's
    integer mask), the CPU lowering one divide."""
    arg = jax.ShapeDtypeStruct((8,), jnp.float32)
    text = jax.jit(fdiv).trace(arg, arg).lower(
        lowering_platforms=(platform,)).as_text()
    assert ("stablehlo.and" in text) is corrected
    assert text.count("stablehlo.divide") == (2 if corrected else 1)
