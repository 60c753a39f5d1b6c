"""Exact float64 predicates over uint32 word pairs (``kernels.f64_words``)
and the 64-bit device buffer form (``exec.device_cache``), checked against
numpy's float64 on random bit patterns and on the edge cases: ties at the
threshold, signed zeros, subnormals, infinities."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.exec.device_cache import device_form, host_form
from repro.exec.refine import f64_sort_key
from repro.kernels import f64_words

RNG = np.random.default_rng(7)
EDGES = np.array([0.0, -0.0, 5e-324, -5e-324, 1e-310, -1e-310,
                  2.2250738585072014e-308, -2.2250738585072014e-308,
                  1.0, -1.0, 1.7976931348623157e308,
                  -1.7976931348623157e308, np.inf, -np.inf])


def _words(k):
    k = np.asarray(k, np.uint64)
    return (jnp.asarray((k >> np.uint64(32)).astype(np.uint32)),
            jnp.asarray((k & np.uint64(0xFFFFFFFF)).astype(np.uint32)))


def _pairs(case):
    """(a, b) float64 arrays, neither NaN, a <= b."""
    if case == "random_bits":
        a = RNG.integers(0, 2**64, 50_000, dtype=np.uint64).view(np.float64)
        b = np.roll(a, 1)
    elif case == "timestamps":
        a = RNG.uniform(1.6e9, 1.8e9, 50_000)
        b = a + RNG.uniform(0, 1200, a.size)
    elif case == "near_threshold":
        a = RNG.uniform(0, 1e4, 20_000)
        a = np.concatenate([a, a, a])
        b = np.concatenate([a[:20_000] + 600.0,
                            np.nextafter(a[:20_000] + 600.0, np.inf),
                            np.nextafter(a[:20_000] + 600.0, -np.inf)])
    elif case == "exponent_gaps":
        a = RNG.uniform(1, 2, 70_000) * 2.0 ** RNG.integers(-20, 20, 70_000)
        b = a * (1 + 2.0 ** -np.repeat(np.arange(70), 1000)
                 * RNG.uniform(0, 2, 70_000))
    elif case == "ties":
        # b − a falls exactly halfway between two doubles 2 apart: the
        # subtraction (a = 1) and the addition (a = −1) must round to even
        b = np.tile(2.0 ** 53 + 2 * np.arange(64), 2)
        a = np.repeat([1.0, -1.0], 64)
    elif case == "edges":
        a, b = (m.ravel() for m in np.meshgrid(EDGES, EDGES))
    else:
        raise ValueError(case)
    ok = ~(np.isnan(a) | np.isnan(b))
    a, b = a[ok], b[ok]
    return np.minimum(a, b), np.maximum(a, b)


CASES = ["random_bits", "timestamps", "near_threshold", "exponent_gaps",
         "ties", "edges"]
THRESHOLDS = [0.0, -1.0, 5e-324, 1e-310, 0.5, 600.0, 1e300, np.inf]
TIE_THRESHOLDS = [2.0 ** 53 + 2 * j for j in range(-1, 66)]


@pytest.mark.parametrize("case", CASES)
def test_span_at_least_matches_numpy(case):
    a, b = _pairs(case)
    ka, kb = _words(f64_sort_key(a)), _words(f64_sort_key(b))
    for d in THRESHOLDS + (TIE_THRESHOLDS if case == "ties" else []):
        with np.errstate(all="ignore"):
            want = (b - a) >= d
        got = np.asarray(f64_words.span_at_least(*ka, *kb, d))
        bad = np.flatnonzero(got != want)
        assert not bad.size, (d, a[bad[0]], b[bad[0]])


@pytest.mark.parametrize("case", CASES)
def test_sort_key_words_match_host(case):
    """key_from_bits ≡ the host sort-key map, bits_from_key inverts it,
    and less_equal / is_nan agree with float64 compares."""
    a, b = _pairs(case)
    a = np.concatenate([a, [np.nan, -np.nan]])
    b = np.concatenate([b, [1.0, np.nan]])
    bits = a.view(np.uint64)
    hi, lo = _words(bits)
    k_hi, k_lo = f64_words.key_from_bits(hi, lo)
    want_hi, want_lo = _words(f64_sort_key(a))
    assert np.array_equal(k_hi, want_hi) and np.array_equal(k_lo, want_lo)
    r_hi, r_lo = f64_words.bits_from_key(k_hi, k_lo)
    back = ((np.asarray(r_hi, np.uint64) << np.uint64(32))
            | np.asarray(r_lo, np.uint64)).view(np.float64)
    assert np.array_equal(back, a + 0.0, equal_nan=True)
    assert np.array_equal(np.asarray(f64_words.is_nan(hi, lo)), np.isnan(a))
    kb = _words(f64_sort_key(b))
    both = ~(np.isnan(a) | np.isnan(b))
    le = np.asarray(f64_words.less_equal(k_hi, k_lo, *kb))
    assert np.array_equal(le[both], (a <= b)[both])


@pytest.mark.parametrize("dtype", [np.float64, np.int64, np.uint64,
                                   np.float32, np.int32, np.bool_])
def test_device_form_round_trips(dtype):
    arr = RNG.integers(0, 2**62, (3, 5)).astype(dtype)
    dev = device_form(arr)
    if arr.dtype.itemsize == 8:
        assert dev.dtype == np.uint32 and dev.shape == (3, 5, 2)
    back = host_form(jnp.asarray(dev), dtype)
    assert back.dtype == arr.dtype and np.array_equal(back, arr)
