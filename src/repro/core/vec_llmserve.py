"""Vectorized geo-distributed LLM serving — ``llmserve_batch`` as a VecEngine.

One routing decision per loop iteration, in submission order, over the
precomputed tables of :mod:`repro.core.llmserve`: the per-stage flow-shop
relay recurrence unrolls at trace time (``n_stages`` is a static), so the
compiled body is a short chain of gathers, adds, maxes and one masked
argmin — no multiplies (everything that needs one, service times, WAN
legs, the KV/locality bias, was multiplied host-side into the tables), so
nothing XLA:CPU could FMA-contract, and ``ops.argmin`` shares the OO
broker's first-occurrence tie rule.  ``oo`` and ``vec`` therefore agree
bit-exactly on every output (differential suite + golden fixture).
Where the backend emulates f64 (the TPU: f32 pairs, not IEEE) the loop
carries the doubles as their int64 bit patterns and adds, maxes and
compares them with :mod:`repro.core.f64bits` — exact integer arithmetic
that yields the IEEE results, so the promise holds on the chip too.

The KV-occupancy counters ride in the carry as i64 (x64 is enabled around
every dispatch) and the all-ineligible (dropped request) case is handled
with ``where`` guards: ``ops.argmin`` returns index 0 on an empty mask —
a valid gather index — and ``any_elig`` masks every committed output.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax.numpy as jnp
import numpy as np

from . import f64bits
from .faults import FaultPlan, RetryPolicy
from .llmserve import (LLMServeBatch, build_cells, empty_llmserve_outputs,
                       packed_layout, summarize)
from .spans import span, tracing
from .vec_engine import BatchPlan, Done, Loop, VecEngine, make_batch_entry


class _Statics(NamedTuple):
    n_requests: int
    n_pipelines: int
    n_stages: int
    use_pallas: bool
    # Static timeout lane (inf = off, keeping the unfaulted compiled graph
    # byte-identical): pipelines that cannot finish a request within
    # ``timeout`` of its submit drop out of the eligible set.  All other
    # fault effects arrive pre-baked in the packed ``eligible`` column.
    timeout: float = math.inf
    # Doubles as int64 bit patterns with f64bits arithmetic (backends
    # without IEEE f64); the tables and the time outputs then travel as bits.
    f64_bits: bool = False


class _Params(NamedTuple):
    """A lane's index into the sweep's request tables (cell axis first):
    the request stream its cell draws and its
    :func:`~repro.core.llmserve.packed_layout` columns up to ``kv_need``.

    The tables themselves are the plan's sweep-wide ``shared`` operand
    (:func:`_tables`), put on the device once a sweep; each lane gathers
    its packed ``[J, K]`` rows from them before its loop (:func:`_rows`).
    Packing everything the body needs for request ``it`` into one row
    turns the loop's per-iteration table access into a *single* dynamic
    slice (instead of eight separate gathers across eight operands), and
    is bit-preserving: the doubles are the same, only their storage
    layout changes.  :func:`summarize` reads the host tables in place."""
    stream: jnp.ndarray       # []   i32 the cell's request stream
    cols: jnp.ndarray         # [K]  i32 its columns in that stream's table


def _tables(cells: LLMServeBatch, exact_bits: bool) -> np.ndarray:
    """The batch's request tables, one ``[C, J]`` per stream (``[U, C,
    J]``), so that a lane's packed rows are whole rows of it.  On the bit
    route the doubles travel as their int64 patterns and the ``kv_need``
    column as the integer it is."""
    tables = np.ascontiguousarray(cells.table.transpose(0, 2, 1))
    if exact_bits:
        tables = f64bits.bits(tables)
        kv_col = cells.cols[0, cells.layout["kv_need"].start]
        tables[:, kv_col] = cells.requests["kv_need"]
    return tables


def _rows(lane: _Params, tables: jnp.ndarray) -> jnp.ndarray:
    """A lane's packed ``[J, K]`` table: its ``K`` columns of its stream's
    table, gathered as whole rows of ``J`` entries (one embedding-style
    gather), then transposed once."""
    n_cols = tables.shape[1]
    flat = tables.reshape(-1, tables.shape[2])            # [U·C, J]
    return flat[lane.stream * n_cols + lane.cols].T


class _Carry(NamedTuple):
    free: jnp.ndarray         # [P, S] f64 time each pipeline stage drains
    kv_used: jnp.ndarray      # [P, S] i64 KV tokens committed per slot
    dst: jnp.ndarray          # [J] i32 chosen pipeline (-1 = dropped)
    finish: jnp.ndarray       # [J] f64 response completion time
    ttft: jnp.ndarray         # [J] f64 time to first token


def _llmserve_build(cell, s: _Statics, ops) -> Loop:
    """One request routed per iteration: the vectorized form of
    :func:`repro.core.llmserve.route_request`, all pipelines at once.
    ``cell`` is the pair ``(lane params, shared request tables)``."""
    packed = _rows(*cell)
    pipes = jnp.arange(s.n_pipelines)
    P, S = s.n_pipelines, s.n_stages
    f = packed_layout(P, S)
    if s.f64_bits:
        add, maximum, key = f64bits.add, f64bits.maximum, f64bits.key
        inf = jnp.int64(f64bits.INF)
        timeout = jnp.int64(f64bits.bits(s.timeout))
    else:
        add, maximum, key = jnp.add, jnp.maximum, (lambda v: v)
        inf, timeout = jnp.inf, s.timeout

    def body(c: _Carry, it) -> _Carry:
        # One dynamic slice fetches everything request `it` needs; the
        # splits below are static (fused into the gather by XLA).
        row = packed[it]                              # [K]
        submit = row[0]
        svc = row[f["svc"]].reshape(P, S)
        hop = row[f["hop"]].reshape(P, S)
        tail = row[f["tail"]]
        first_extra = row[f["first_extra"]]
        bias = row[f["bias"]]
        elig = row[f["eligible"]] != 0
        kv_need = row[-1].astype(c.kv_used.dtype)     # the last field
        # Store-and-forward relay through the pipeline stages (unrolled at
        # trace time): depart(s) = max(free[s], depart(s-1)+hop[s]) + svc[s].
        d = jnp.broadcast_to(submit, (P,))
        start_last = d
        deps = []
        for st in range(S):
            arr = add(d, hop[:, st])
            start_last = maximum(c.free[:, st], arr)
            d = add(start_last, svc[:, st])
            deps.append(d)
        dep = jnp.stack(deps, axis=1)                 # [P, S]
        fin = add(d, tail)
        if math.isfinite(s.timeout):                  # static: timeout lane
            elig = elig & (key(fin) <= key(add(submit, timeout)))
        # Masked like ops.argmin's own fill, in key order for bit patterns.
        pick = ops.argmin(jnp.where(elig, key(add(fin, bias)), key(inf)))
        ok = jnp.any(elig)
        sel = (pipes[:, None] == pick) & ok           # [P, S]
        return _Carry(
            free=jnp.where(sel, dep, c.free),
            kv_used=c.kv_used + jnp.where(sel, kv_need, 0),
            dst=c.dst.at[it].set(
                jnp.where(ok, pick, -1).astype(jnp.int32)),
            finish=c.finish.at[it].set(jnp.where(ok, fin[pick], inf)),
            ttft=c.ttft.at[it].set(jnp.where(
                ok, add(start_last[pick], first_extra[pick]), inf)))

    dtype = packed.dtype
    return Loop(
        init=_Carry(free=jnp.zeros((P, S), dtype),
                    kv_used=jnp.zeros((P, S), jnp.int64),
                    dst=jnp.full((s.n_requests,), -1, jnp.int32),
                    finish=jnp.full((s.n_requests,), inf, dtype),
                    ttft=jnp.full((s.n_requests,), inf, dtype)),
        cond=lambda c, it: it < s.n_requests,
        body=body,
        finalize=lambda c, it: dict(dst=c.dst, finish=c.finish,
                                    ttft=c.ttft, kv_used=c.kv_used),
        trip_count=s.n_requests)


LLMSERVE_ENGINE = VecEngine("llmserve_batch", _llmserve_build)


def _prepare_llmserve(*, use_pallas: bool, seeds=(0,), n_machines: int = 6,
                      n_regions: int = 3, n_stages: int = 2,
                      n_pipelines=None, n_layers: int = 32,
                      n_requests: int = 64, placement=None, machines=None,
                      mean_gap_s=1.0, locality_weight=1.0,
                      offline_region=-1, offline_frac: float = 0.25,
                      slo_ttft_s: float = 5.0, kv_penalty_s: float = 0.5,
                      link_bw: float = 10e9, hop_latency_s: float = 0.03,
                      prompt_tokens=(64, 1024), decode_tokens=(16, 512),
                      fault_plan: Optional[FaultPlan] = None,
                      retry: Optional[RetryPolicy] = None,
                      timeout_s: float = math.inf, workload=None):
    with span("sweep.prepare.build") as build:
        cells, b = build_cells(
            seeds=seeds, n_machines=n_machines, n_regions=n_regions,
            n_stages=n_stages, n_pipelines=n_pipelines, n_layers=n_layers,
            n_requests=n_requests, placement=placement, machines=machines,
            mean_gap_s=mean_gap_s, locality_weight=locality_weight,
            offline_region=offline_region, offline_frac=offline_frac,
            slo_ttft_s=slo_ttft_s, kv_penalty_s=kv_penalty_s,
            link_bw=link_bw, hop_latency_s=hop_latency_s,
            prompt_tokens=prompt_tokens, decode_tokens=decode_tokens,
            fault_plan=fault_plan, retry=retry, timeout_s=timeout_s,
            workload=workload)
        if b and tracing():                # distinct request streams drawn
            build.set_metadata(workloads=len(cells.table))
    if b == 0:
        return Done(empty_llmserve_outputs(
            int(n_machines), faulted=fault_plan is not None
            or math.isfinite(timeout_s)))
    exact_bits = not f64bits.native()
    n_pipes, n_st = cells.placement.shape[1:]
    k = cells.layout["kv_need"].stop
    lanes = _Params(stream=cells.stream.astype(np.int32),
                    cols=cells.cols[:, :k].astype(np.int32))
    # Every lane routes exactly n_requests requests (an injected workload
    # sets its own): nothing to bucket.
    return BatchPlan(lanes,
                     _Statics(cells.table.shape[1], int(n_pipes), int(n_st),
                              bool(use_pallas), timeout=cells.timeout_s,
                              f64_bits=exact_bits),
                     finalize=lambda out: summarize(
                         _doubles(out) if exact_bits else out, cells),
                     shared=_tables(cells, exact_bits))


def _doubles(out):
    """The bit-pattern route's time outputs back as doubles."""
    return dict(out, **{k: f64bits.doubles(out[k])
                        for k in ("finish", "ttft")})


simulate_llmserve_batch = make_batch_entry(
    LLMSERVE_ENGINE, _prepare_llmserve, name="simulate_llmserve_batch",
    doc="""\
    Batched geo-distributed LLM serving through the sweep layer.

    ``seeds`` and the sweep axes ``mean_gap_s`` / ``locality_weight`` /
    ``offline_region`` (scalars or arrays broadcast against ``seeds``)
    define the batch; ``placement`` may additionally carry a leading cell
    axis (``[B, P, S]``) for placement-search grids.  Each cell's request
    stream and routing tables come from :mod:`repro.core.llmserve` and are
    shared verbatim with the OO reference broker.  Returns per-request
    ``dst``/``finish``/``ttft`` and per-slot ``kv_used`` plus the shared
    serving summary (``served``, ``dropped``, ``makespan``,
    ``latency_mean_s``, ``ttft_mean_s``, ``slo_violations``,
    ``tokens_out``, ``pipe_requests``, ``machine_busy_s``,
    ``kv_assigned_tokens``, ``utilization``, ``wan_delay_total_s``, …);
    ``with_report=True`` adds the ``SweepReport``.
    A ``fault_plan`` (:class:`~repro.core.faults.FaultPlan` of ``node`` /
    ``region`` / ``link`` / ``transient`` windows), ``retry``
    (:class:`~repro.core.faults.RetryPolicy`) and ``timeout_s`` inject
    machine crashes, regional outages, WAN degradation and transient
    request failures; faulted runs add ``submit`` / ``retries`` outputs.
    Bit-exact vs the ``oo``/``legacy`` backends on every output.
    """)
