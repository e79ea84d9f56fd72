"""IEEE binary64 on int64 bit patterns (``repro.core.f64bits``): every sum
bit-identical to the host's IEEE doubles, keys ordered as the values."""
import jax
import numpy as np
import pytest

from repro.core import f64bits
from repro.core.vec_engine import x64

N = 200_000


def _random_bits(rng, n):
    b = rng.integers(0, 2**63 - 1, n, dtype=np.int64)
    b |= rng.integers(0, 2, n).astype(np.int64) << 63
    x = f64bits.doubles(b).copy()
    return np.where(np.isfinite(x), x, 1.0)


def _operands(name, rng):
    if name == "wide":               # exponent gaps both ways, both signs
        return [rng.standard_normal(N) * 10.0 ** rng.integers(-6, 7, N)
                for _ in range(2)]
    if name == "cancel":             # near cancellation: left renormalizing
        a = rng.standard_normal(N)
        return a, -a * (1 + rng.integers(-64, 65, N) * 2.0 ** -52)
    if name == "bit_patterns":       # every finite pattern, overflow included
        return _random_bits(rng, N), _random_bits(rng, N)
    if name == "subnormal":
        return [rng.integers(-2**53, 2**53, N) * 5e-324 for _ in range(2)]
    if name == "rounding_ties":      # halfway cases, round to even
        return (rng.integers(1, 2**20, N) * 2.0 ** 33,
                rng.integers(1, 2**20, N) * 2.0 ** -40)
    special = np.array([0.0, -0.0, np.inf, -np.inf, 1.0, -1.0, 5e-324,
                        -5e-324, 2.2250738585072014e-308,
                        1.7976931348623157e308, -1.7976931348623157e308])
    a, b = (m.ravel() for m in np.meshgrid(special, special))
    keep = ~(np.isinf(a) & np.isinf(b) & (a != b))     # inf + -inf: NaN
    return a[keep], b[keep]


@pytest.mark.parametrize("name", ["wide", "cancel", "bit_patterns",
                                  "subnormal", "rounding_ties", "special"])
def test_add_is_ieee(name):
    a, b = _operands(name, np.random.default_rng(sum(map(ord, name))))
    with x64():
        got = np.asarray(jax.jit(f64bits.add)(f64bits.bits(a),
                                              f64bits.bits(b)))
    with np.errstate(over="ignore"):
        want = f64bits.bits(a + b)
    bad = np.flatnonzero(got != want)
    assert bad.size == 0, [(a[i], b[i], f64bits.doubles(got[i]), a[i] + b[i])
                           for i in bad[:5]]


def test_key_orders_as_the_values_and_maximum_agrees():
    rng = np.random.default_rng(3)
    v = np.concatenate([_operands("special", rng)[0],
                        rng.standard_normal(N) * 10.0 ** rng.integers(-300, 300, N)])
    w = rng.permutation(v)
    with x64():
        k = np.asarray(f64bits.key(f64bits.bits(v)))
        mx = f64bits.doubles(np.asarray(
            f64bits.maximum(f64bits.bits(v), f64bits.bits(w))))
    s = v[np.argsort(k, kind="stable")]
    assert np.all(s[1:] >= s[:-1])
    np.testing.assert_array_equal(mx, np.maximum(v, w))


def test_native_is_false_only_on_tpu(monkeypatch):
    assert f64bits.native() == (jax.default_backend() != "tpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert not f64bits.native()
