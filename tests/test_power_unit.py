"""Direct unit tests for ``core.power`` models and ``core.selection``.

The power models and selection policies were previously exercised only
through system paths (``test_selection_power.py`` consolidation runs);
these pin their contracts directly: SPEC-table interpolation endpoints,
DVFS monotonicity, the segment-sum energy decomposition, and the
selection policies' first-occurrence tie-breaking (which the vec engine's
``argmin``/``argmax`` mirrors).
"""
import math

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.power import (SPEC_HP_ML110_G4, SPEC_HP_ML110_G5,
                              PowerModelCubic, PowerModelDvfs,
                              PowerModelLinear, PowerModelSpecTable,
                              interp_table, make_power_fleet, power_points,
                              segment_energy_j, table_segment)
from repro.core.selection import (MaximumScore, MinimumScore,
                                  least_power_efficient,
                                  most_power_efficient)


# -- SPEC-table interpolation --------------------------------------------------

def test_spec_table_endpoints():
    m = PowerModelSpecTable(SPEC_HP_ML110_G4)
    assert m.power(0.0) == SPEC_HP_ML110_G4[0] == 86.0
    assert m.power(1.0) == SPEC_HP_ML110_G4[-1] == 117.0
    # every measurement point is reproduced exactly
    for k, p in enumerate(SPEC_HP_ML110_G4):
        assert m.power(k / 10) == p


def test_spec_table_interpolates_linearly_between_points():
    m = PowerModelSpecTable(SPEC_HP_ML110_G5)
    mid = 0.5 * (SPEC_HP_ML110_G5[3] + SPEC_HP_ML110_G5[4])
    assert m.power(0.35) == pytest.approx(mid, rel=1e-15)


def test_interp_table_clamps_out_of_range():
    pts = SPEC_HP_ML110_G4
    assert interp_table(pts, -0.5) == pts[0]
    assert interp_table(pts, 1.5) == pts[-1]


def test_spec_table_rejects_degenerate():
    with pytest.raises(ValueError):
        PowerModelSpecTable((100.0,))


# -- linear / cubic ------------------------------------------------------------

def test_linear_and_cubic_share_endpoints_cubic_lower_midrange():
    lin = PowerModelLinear(86.0, 117.0)
    cub = PowerModelCubic(86.0, 117.0)
    assert lin.power(0.0) == cub.power(0.0) == 86.0
    assert lin.power(1.0) == cub.power(1.0) == 117.0
    for u in (0.25, 0.5, 0.75):       # u³ < u on (0, 1)
        assert cub.power(u) < lin.power(u)


# -- DVFS ----------------------------------------------------------------------

def test_dvfs_monotone_nondecreasing():
    m = PowerModelDvfs(86.0, 117.0, steps=(0.4, 0.6, 0.8, 1.0))
    grid = np.linspace(0.0, 1.0, 401)
    powers = [m.power(float(u)) for u in grid]
    assert all(b >= a for a, b in zip(powers, powers[1:]))
    assert powers[0] == 86.0                      # idle at zero load
    assert powers[-1] == 117.0                    # full power at full load


def test_dvfs_frequency_steps():
    m = PowerModelDvfs(steps=(0.5, 1.0))
    assert m.frequency(0.0) == 0.5
    assert m.frequency(0.5) == 0.5
    assert m.frequency(0.50001) == 1.0
    # below the step boundary the host clocks down: cheaper than linear
    lin = PowerModelLinear(m.idle_w, m.max_w)
    assert m.power(0.3) < lin.power(0.3)


def test_dvfs_rejects_bad_steps():
    with pytest.raises(ValueError):
        PowerModelDvfs(steps=(0.8, 0.4, 1.0))     # not ascending
    with pytest.raises(ValueError):
        PowerModelDvfs(steps=(0.4, 0.8))          # doesn't end at 1.0


# -- table sampling + segment-sum energy decomposition -------------------------

def test_power_points_roundtrips_spec_table():
    m = PowerModelSpecTable(SPEC_HP_ML110_G4)
    assert tuple(power_points(m, 11)) == SPEC_HP_ML110_G4
    with pytest.raises(ValueError):
        power_points(m, 1)


def test_table_segment_matches_direct_interpolation():
    """Σ-by-segment energy (what both engines accumulate) equals the direct
    per-interval interpolation bit-for-bit."""
    rng = np.random.default_rng(3)
    pts = np.asarray(power_points(PowerModelCubic(90.0, 130.0), 11))
    for util in [0.0, 0.05, 0.1, 0.5, 0.999, 1.0, *rng.uniform(0, 1, 20)]:
        s, frac = table_segment(float(util), 11)
        seg_count = np.zeros((1, 10)); seg_count[0, s] = 1
        seg_frac = np.zeros((1, 10)); seg_frac[0, s] = frac
        e = segment_energy_j(pts[None], seg_count, seg_frac, 300.0)[0]
        assert e == interp_table(pts, float(util)) * 300.0, util


def test_table_segment_top_endpoint():
    s, frac = table_segment(1.0, 11)
    assert (s, frac) == (9, 1.0)                  # folds into last segment
    s, frac = table_segment(0.0, 11)
    assert (s, frac) == (0, 0.0)


def test_table_segment_frac_equals_direct_difference():
    # fmod(x, 1) must equal the x - ⌊x⌋ a direct interpolation uses
    for u in np.linspace(0.0, 0.9999, 57):
        x = float(u) * 10
        s, frac = table_segment(float(u), 11)
        assert frac == x - math.floor(x)


# -- the vec loop's decomposition under a cast that may round ----------------

P = 11
WITNESS = 0.9999999932878628      # a 2-VM host just below the 10 % knot
CASTS = {
    "exact": lambda x: x.astype(np.int32),
    # the TPU's f64 is an f32 pair; its cast reads the high word, which
    # rounds to nearest: 1 - 6.7e-9 casts to 1
    "chip": lambda x: x.astype(np.float32).astype(np.int32),
    # one below the floor, for the comparison that takes a cast back up
    "low": lambda x: np.maximum(x.astype(np.int32) - 1, 0),
}


def _knots_and_neighbours():
    # Above 0, the least normal double: XLA flushes subnormals to zero.
    xs = [WITNESS, 0.0, np.finfo(np.float64).tiny]
    for k in range(1, P):
        xs += [float(k), np.nextafter(float(k), 0.0),
               np.nextafter(float(k), 11.0)]
    xs += list(np.random.default_rng(7).uniform(0, P - 1, 200))
    return np.clip(np.asarray(xs, np.float64), 0.0, P - 1)


@pytest.mark.parametrize("cast", sorted(CASTS))
def test_vec_table_segment_equals_floor_and_fmod(cast):
    """The loop's ``(seg, frac)`` is the reference's ``min(int(x), P-2)``
    and ``fmod(x, 1)`` bit for bit, whichever way the int cast rounded."""
    from repro.core import vec_engine
    from repro.core.vec_power import table_segment as vec_table_segment
    x = _knots_and_neighbours()
    want_seg = np.minimum(x.astype(np.int64), P - 2)
    want_frac = np.where(x >= P - 1, 1.0, np.fmod(x, 1.0))
    with vec_engine.x64():
        seg, frac = vec_table_segment(jnp.asarray(x),
                                      jnp.asarray(CASTS[cast](x)), P)
    seg, frac = np.asarray(seg), np.asarray(frac)
    assert frac.dtype == np.float64
    assert np.array_equal(seg, want_seg)
    assert frac.tobytes() == want_frac.tobytes()
    at = {float(v): (int(a), float(b)) for v, a, b in zip(x, seg, frac)}
    assert at[WITNESS] == (0, WITNESS)
    assert at[0.0] == (0, 0.0)
    assert at[P - 1.0] == (P - 2, 1.0)


def test_chip_cast_rounds_the_witness_up():
    """What the chip-like cast above stands for: the witness casts to the
    knot above it, so a segment taken from the cast alone is one high."""
    assert CASTS["chip"](np.float64(WITNESS)) == 1
    assert CASTS["exact"](np.float64(WITNESS)) == 0


# -- fleet factory -------------------------------------------------------------

def test_make_power_fleet_mixes_all_families():
    fleet = make_power_fleet(8, "mixed")
    kinds = {type(m).__name__ for m in fleet}
    assert kinds == {"PowerModelLinear", "PowerModelCubic",
                     "PowerModelSpecTable", "PowerModelDvfs"}
    with pytest.raises(ValueError):
        make_power_fleet(4, "nuclear")


# -- selection tie-breaking ----------------------------------------------------

def test_min_max_score_first_occurrence_tie_break():
    """Ties select the *first* extremal candidate — the documented contract
    the vec engine's first-occurrence argmin/argmax reproduces."""
    items = ["a", "b", "c", "d"]
    scores = {"a": 2.0, "b": 1.0, "c": 1.0, "d": 2.0}
    assert MinimumScore(scores.get).select(items) == "b"
    assert MaximumScore(scores.get).select(items) == "a"
    # all-tied pools pick the first element outright
    assert MinimumScore(lambda x: 0.0).select(items) == "a"
    assert MaximumScore(lambda x: 0.0).select(items) == "a"


def test_energy_aware_selectors_match_argmin_argmax():
    eff = np.array([1.5, 0.9, 0.9, 1.5, 2.0])
    hosts = list(range(len(eff)))
    on = most_power_efficient(lambda i: eff[i]).select(hosts)
    off = least_power_efficient(lambda i: eff[i]).select(hosts)
    assert on == int(np.argmin(eff)) == 1         # first of the 0.9 tie
    assert off == int(np.argmax(eff)) == 4
    # tie on the maximum side: first occurrence again
    eff2 = np.array([2.0, 1.0, 2.0])
    assert least_power_efficient(lambda i: eff2[i]).select([0, 1, 2]) == 0
