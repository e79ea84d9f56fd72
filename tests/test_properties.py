"""Property-based tests — skipped cleanly when ``hypothesis`` is absent.

These lived in test_engine.py / test_substrate.py; they are grouped here so
a machine without the optional dev dependency still collects and runs the
full deterministic suite (``pip install -r requirements-dev.txt`` brings
hypothesis in).
"""
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from repro.core.backend import run_scenario
from repro.core.events import Event, HeapEventQueue, LinkedListEventQueue
from repro.core.vec_scheduler import simulate_batch
from repro.optim import compress_int8, decompress_int8


# -- event queues -------------------------------------------------------------

@given(st.lists(st.tuples(st.floats(0, 1e6, allow_nan=False),
                          st.integers(0, 3)), max_size=200))
@settings(max_examples=50, deadline=None)
def test_queue_pop_order_property(items):
    """Both queues pop in (time, priority, insertion) order — identically."""
    heap, ll = HeapEventQueue(), LinkedListEventQueue()
    for t, pr in items:
        heap.push(Event(time=t, tag="x", priority=pr))
        ll.push(Event(time=t, tag="x", priority=pr))
    out_h = [heap.pop().sort_key() for _ in range(len(items))]
    out_l = [ll.pop().sort_key() for _ in range(len(items))]
    assert out_h == sorted(out_h)
    assert out_h == out_l


# -- vectorized scheduler vs OO engine (property) --------------------------------

@given(st.integers(0, 2 ** 31 - 1), st.sampled_from(["time", "space"]))
@settings(max_examples=15, deadline=None)
def test_vec_scheduler_matches_oo(seed, mode):
    rng = np.random.default_rng(seed)
    G, C = 2, 5
    length = np.where(rng.random((G, C)) < 0.8,
                      rng.integers(100, 5000, (G, C)).astype(float), 0.0)
    pes = rng.integers(1, 3, (G, C)).astype(float)
    submit = np.where(length > 0, np.round(rng.random((G, C)) * 10, 3), 1e18)
    gmips = rng.integers(500, 2000, G).astype(float)
    gpes = rng.integers(1, 5, G).astype(float)
    vec = simulate_batch(length, pes, submit, gmips, gpes, mode)
    # Reference semantics via the backend substrate's OO handler (the same
    # path tests/test_vec_scheduler_edges.py exercises).
    oo = run_scenario("cloudlet_batch", backend="oo", length=length, pes=pes,
                      submit=submit, guest_mips=gmips, guest_pes=gpes,
                      mode=mode)
    for g in range(G):
        for c in range(C):
            assert np.isclose(vec[g, c], oo[g, c], rtol=1e-9, atol=1e-9) or \
                (np.isinf(vec[g, c]) and np.isinf(oo[g, c]))


# -- compression --------------------------------------------------------------

@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=20, deadline=None)
def test_int8_roundtrip_error_bound(seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(64,)) * rng.uniform(0.01, 10))
    q, scale = compress_int8(x)
    back = decompress_int8(q, scale)
    assert float(jnp.max(jnp.abs(back - x))) <= float(scale) * 0.5 + 1e-9


# -- Eq.(2) as a property over random parameters -------------------------------

@given(payload=st.floats(1.0, 2e9), overhead=st.floats(0.0, 10.0),
       length=st.floats(100.0, 1e6))
@settings(max_examples=20, deadline=None)
def test_eq2_property(payload, overhead, length):
    """Simulated chain makespan equals Eq.(2) for arbitrary parameters."""
    import repro.core.case_study as cs
    from repro.core.network import theoretical_makespan
    old_l = cs.L_TASK
    try:
        cs.L_TASK = length
        for placement, hops in (("I", 0), ("II", 1), ("III", 2)):
            r = cs.run_case_study(virt="V", placement=placement,
                                  payload=payload, activations=1)
            theo = theoretical_makespan([length, length], cs.MIPS,
                                        cs.O_V, hops, payload, cs.BW)
            assert abs(r.makespans[0] - theo) < 1e-6 * max(theo, 1.0)
    finally:
        cs.L_TASK = old_l


# -- selection invariants -------------------------------------------------------

@given(st.lists(st.floats(-1e6, 1e6), min_size=1, max_size=50))
@settings(max_examples=50, deadline=None)
def test_minmax_score_invariant(xs):
    from repro.core.selection import MaximumScore, MinimumScore
    lo = MinimumScore(lambda x: x).select(xs)
    hi = MaximumScore(lambda x: x).select(xs)
    assert lo == min(xs) and hi == max(xs)


@given(st.lists(st.integers(-100, 100), min_size=1, max_size=50))
@settings(max_examples=50, deadline=None)
def test_filter_respected(xs):
    from repro.core.selection import MinimumScore
    sel = MinimumScore(lambda x: x).select(xs, lambda x: x % 2 == 0)
    evens = [x for x in xs if x % 2 == 0]
    assert sel == (min(evens) if evens else None)


# -- sharding resolution --------------------------------------------------------

@given(st.integers(1, 64), st.integers(1, 64))
@settings(max_examples=30, deadline=None)
def test_resolve_spec_never_errors(d1, d2):
    import jax
    from repro.distributed.sharding import LOGICAL_RULES_BASE, resolve_spec
    mesh = jax.sharding.AbstractMesh((2, 4), ("data", "model"))
    spec = resolve_spec((d1, d2), ("mlp", "embed"), mesh, LOGICAL_RULES_BASE)
    assert len(spec) == 2
