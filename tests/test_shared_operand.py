"""A plan's sweep-wide ``shared`` operand through the sweep layer.

``BatchPlan.shared`` is a pytree with no cell axis that every lane reads:
it goes to the device once a sweep and rides beside the lane params in
every executable call, chunked (padded last chunk included) and
compacting (refills, quarantine, a retried segment).  A toy engine reads
its lane's row of a shared table; the same plan with that row packed per
lane is the reference, and the two must agree bit for bit.
"""
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import vec_engine
from repro.core.vec_engine import BatchPlan, Loop, VecEngine, run_plan

U, J, B = 3, 12, 11                 # table rows, row width, cells


class _Lane(NamedTuple):
    row: np.ndarray                 # [] i32 the shared table's row it reads
    length: np.ndarray              # [] i32 iterations the lane runs


class _Packed(NamedTuple):
    values: np.ndarray              # [J] f64 that row, packed per lane
    length: np.ndarray


class _Statics(NamedTuple):
    width: int


def _loop(values, length) -> Loop:
    return Loop(init=jnp.zeros((), values.dtype),
                cond=lambda c, it: it < length,
                body=lambda c, it: c * 1.5 + values[it],
                finalize=lambda c, it: dict(total=c))


SHARED = VecEngine("shared_toy", lambda cell, s, ops: _loop(
    cell[1][cell[0].row], cell[0].length))
PACKED = VecEngine("packed_toy", lambda cell, s, ops: _loop(
    cell.values, cell.length))


def _plans(poison: bool = False):
    rng = np.random.default_rng(11)
    table = rng.uniform(-1.0, 1.0, (U, J))
    row = rng.integers(0, U, B).astype(np.int32)
    row[:3] = [0, 1, 2]
    length = rng.integers(1, J + 1, B).astype(np.int32)
    length[2] = J
    if poison:
        table[2, J - 1] = np.nan        # every lane that reaches it
    cost = length.astype(np.float64)
    return (BatchPlan(_Lane(row, length), _Statics(J), cost, shared=table),
            BatchPlan(_Packed(table[row], length), _Statics(J), cost))


def _same(a: dict, b: dict):
    assert a.keys() == b.keys()
    for k in a:
        x, y = np.asarray(a[k]), np.asarray(b[k])
        assert x.dtype == y.dtype and x.tobytes() == y.tobytes(), k


def _put_counter(monkeypatch, table):
    """Counts the copies of ``table`` put on the device."""
    put, real = [], jax.device_put

    def spy(x, *a, **k):
        put.extend(leaf for leaf in jax.tree_util.tree_leaves(x)
                   if np.shape(leaf) == table.shape)
        return real(x, *a, **k)
    monkeypatch.setattr(jax, "device_put", spy)
    return put


@pytest.mark.parametrize("chunk_size", [None, 4])
def test_chunked_shared_matches_packed(chunk_size):
    """Monolithic, and in chunks of 4 over 11 cells (the last one padded):
    the same outputs as the packed plan, the table sent once a sweep and
    counted in ``h2d_bytes``, one lane-params upload per chunk."""
    shared, packed = _plans()
    out, rep = run_plan(SHARED, shared, chunk_size=chunk_size,
                        with_report=True)
    ref, ref_rep = run_plan(PACKED, packed, chunk_size=chunk_size,
                            with_report=True)
    _same(out, ref)
    assert rep.n_chunks == ref_rep.n_chunks == (1 if chunk_size is None
                                                else 3)
    assert rep.shared_bytes == shared.shared.nbytes
    lane_bytes = rep.chunk_size * 8               # row and length, i32
    assert rep.param_uploads == ref_rep.param_uploads == rep.n_chunks
    assert rep.h2d_bytes == rep.shared_bytes + rep.n_chunks * lane_bytes
    assert rep.report_fields()["shared_bytes"] == rep.shared_bytes


def test_compact_refills_send_the_table_once(monkeypatch):
    """Compacting over 4 lanes with refills: the lane params go again after
    each refill, the shared table never does."""
    shared, packed = _plans()
    ref, ref_rep = run_plan(PACKED, packed, compact=True, chunk_size=4,
                            segment_iters=3, with_report=True)
    put = _put_counter(monkeypatch, shared.shared)
    out, rep = run_plan(SHARED, shared, compact=True, chunk_size=4,
                        segment_iters=3, with_report=True)
    _same(out, ref)
    assert rep.refills == ref_rep.refills == B - 4
    assert rep.param_uploads == ref_rep.param_uploads > 1
    assert len(put) == 1 and rep.shared_bytes == shared.shared.nbytes


def test_quarantine_and_a_retried_segment_reuse_the_table(monkeypatch):
    """A NaN in the table poisons the lanes that read it; they are
    quarantined as with the packed plan, and a segment that raises is
    retried with the device copy of the table it already has."""
    shared, packed = _plans(poison=True)
    ref, ref_rep = run_plan(PACKED, packed, compact=True, chunk_size=4,
                            segment_iters=3, with_report=True,
                            quarantine=True)
    calls, real = [], vec_engine.segment_step

    def failing_once(*a, **k):
        step = real(*a, **k)

        def stepped(*args):
            calls.append(len(args))
            if len(calls) == 2:
                raise RuntimeError("segment lost")
            return step(*args)
        return stepped
    monkeypatch.setattr(vec_engine, "segment_step", failing_once)
    put = _put_counter(monkeypatch, shared.shared)
    out, rep = run_plan(SHARED, shared, compact=True, chunk_size=4,
                        segment_iters=3, with_report=True, quarantine=True)
    _same(out, ref)
    assert rep.retried_segments == 1 and ref_rep.retried_segments == 0
    assert rep.quarantined == ref_rep.quarantined > 0
    assert np.array_equal(rep.quarantined_cells, ref_rep.quarantined_cells)
    assert set(calls) == {6}        # lane params, state, it, fresh, sink, table
    assert len(put) == 1 and rep.shared_bytes == shared.shared.nbytes


@pytest.mark.parametrize("compact", [False, True])
def test_plan_without_shared_counts_no_shared_bytes(compact):
    """A plan without ``shared`` sends none and reports 0; its lane params
    go as often as the shared plan's do."""
    shared, packed = _plans()
    kw = dict(compact=compact, chunk_size=4, segment_iters=3,
              with_report=True)
    _, rep = run_plan(SHARED, shared, **kw)
    _, ref_rep = run_plan(PACKED, packed, **kw)
    assert ref_rep.shared_bytes == 0
    assert ref_rep.report_fields()["shared_bytes"] == 0
    assert ref_rep.param_uploads == rep.param_uploads
    if not compact:
        assert ref_rep.param_uploads == ref_rep.n_chunks == 3
        assert ref_rep.h2d_bytes == 3 * 4 * (J * 8 + 4)


def test_state_prototype_reads_shapes_only():
    """The loop state's shapes come from one lane's shapes and the shared
    operand's, device-resident ones included, without a copy back."""
    shared, _ = _plans()
    with vec_engine.x64():
        lanes = jax.device_put(shared.params)
        proto = vec_engine.state_prototype(SHARED, shared.statics, lanes,
                                           jax.device_put(shared.shared))
    assert proto.shape == () and proto.dtype == np.float64
