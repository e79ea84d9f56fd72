"""The reduction from a trace to per-layer numbers."""
import pathlib

import jax
import jax.numpy as jnp
import pytest

from bench import harness, spec, tracereduce
from bench.tracereduce import Recorded, clip, merge, reduce

DATA = pathlib.Path(__file__).resolve().parent / "data"
READERS = ("prep_ms", "loop_ms", "sched_gap_ms", "finalize_ms",
           "device_idle_share")


def _reader(name):
    return spec.load_module(spec.ROOT / "bench" / "metrics" / f"{name}.py")


def test_merge_and_clip():
    assert merge([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5), (3, 4)]
    assert clip([(0, 2.5), (3, 4)], 1, 3.5) == [(1, 2.5), (3, 3.5)]


def test_phases_add_up_and_gaps_are_labelled():
    # Two sweeps on one chip; sweep 7 has two device bursts (a segment
    # round trip between them), sweep 8 one.
    rec = Recorded(spans=[(7, 0.0, 1.0), (8, 1.5, 2.0)],
                   ops=[(0, "fusion.1", 0.2, 0.4), (0, "fusion.2", 0.3, 0.5),
                        (0, "while.3", 0.7, 0.9), (0, "fusion.1", 1.6, 1.7)])
    t = reduce(rec, n_chips=1)
    assert t.window_s == pytest.approx(2.0)
    assert t.busy_s == pytest.approx(0.3 + 0.2 + 0.1)
    p7, p8 = t.phases
    assert (p7.prep_s, p7.loop_s, p7.gap_s, p7.finalize_s) == pytest.approx(
        (0.2, 0.5, 0.2, 0.1))
    assert (p8.prep_s, p8.loop_s, p8.gap_s, p8.finalize_s) == pytest.approx(
        (0.1, 0.1, 0.0, 0.3))
    for p, (_, s, e) in zip(t.phases, rec.spans):
        assert p.prep_s + p.loop_s + p.gap_s + p.finalize_s == \
            pytest.approx(e - s)
    gaps = dict(t.idle_gaps)
    assert gaps["between@sweep7"] == pytest.approx(0.5)
    assert gaps["scheduler@sweep7"] == pytest.approx(0.2)
    assert gaps["finalize@sweep8"] == pytest.approx(0.3)
    assert t.device_ops[0] == ("fusion.1", pytest.approx(0.3))
    assert _reader("device_idle_share").read(t) == pytest.approx(1 - 0.6 / 2)
    assert _reader("sched_gap_ms").read(t) == pytest.approx(100.0)


def test_busy_is_averaged_over_chips():
    rec = Recorded(spans=[(2, 0.0, 1.0)],
                   ops=[(0, "a", 0.0, 0.5), (1, "a", 0.25, 1.0)])
    t = reduce(rec, n_chips=2)
    assert t.busy_s == pytest.approx((0.5 + 0.75) / 2)
    assert t.phases[0].loop_s == pytest.approx(1.0)     # union over chips


def test_nothing_to_read_gives_no_metric():
    assert reduce(Recorded(spans=[(2, 0.0, 1.0)], ops=[]), 1) is None
    empty = tracereduce.Reduced(n_chips=1, window_s=1.0, busy_s=0.0)
    for name in READERS:
        assert _reader(name).read(empty) is None


@pytest.mark.parametrize("path", sorted(DATA.glob("recorded_*.json")),
                         ids=lambda p: p.stem)
def test_recorded_chip_trace(path):
    """Two sweeps recorded on a TPU v5e (operations nested inside an
    enclosing one on the same chip left out, which keeps the union): every
    metric reads, the phases of each sweep add up to its span, and no share
    passes 1."""
    rec = Recorded.from_json(path.read_text())
    t = reduce(rec, n_chips=1)
    assert t is not None and len(t.phases) == len(rec.spans)
    for p, (_, s, e) in zip(t.phases, rec.spans):
        assert min(p.prep_s, p.loop_s, p.gap_s, p.finalize_s) >= 0
        assert p.prep_s + p.loop_s + p.gap_s + p.finalize_s == \
            pytest.approx(e - s)
    values = {n: _reader(n).read(t) for n in READERS}
    assert all(v is not None and v >= 0 for v in values.values())
    assert 0 < values["device_idle_share"] < 1
    assert len(t.device_ops) <= 10 and len(t.idle_gaps) <= 10


def test_counter_sees_a_compile_and_no_cached_call():
    counter = harness.Counter()
    f = jax.jit(lambda x: x * 3 + 1)
    x, y = jnp.arange(5.0), jnp.arange(5.0) + 1
    c0 = counter.compiles
    f(x).block_until_ready()
    assert counter.compiles == c0 + 1
    f(y).block_until_ready()                            # cached shape
    assert counter.compiles == c0 + 1
