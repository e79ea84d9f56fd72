"""Vectorized networked-workflow engine — DAG stage machines as JAX SoA.

The OO path runs the NetworkCloudSim rewrite (``core.workflow`` +
``core.datacenter``) one Python event at a time.  This module is the same
EXEC/SEND/RECV stage semantics — Algorithm 1's handler methods, time-shared
capacity splitting, store-and-forward link delays with composed
virtualization overheads (C4) — as a :class:`~repro.core.vec_engine
.VecEngine` definition, so the whole §6 case-study grid (virt × placement ×
payload × seed) runs in a single compiled call.

SoA layout (per scenario cell): each DAG activation is flattened into tasks
``[n_tasks]`` with padded stage columns ``[n_tasks, max_stages]`` (``kind``,
EXEC MI ``slen`` + ordered prefix ``before``, closed-form SEND ``delay``
from :func:`repro.core.network.store_and_forward_delay`, matching RECV slot
coordinates); packet transport is a scatter of arrival times, and the next
event is a masked min over (EXEC finish estimates, future submissions,
in-flight arrivals) via ``ops.min``.

Exactness contract (asserted by tests): deterministic single-activation
DAGs are **bit-identical** to the OO engine and equal to
``theoretical_makespan`` (Eq. 2) where it applies; Poisson activation
streams share the OO arrival draws and match within 2% mean over ≥64
seeds.  Documented approximations (second-order; none hit by the
case-study grid): host oversubscription folded into static granted MIPS;
≥3-PE guests may differ in the last ulp; zero-span submission re-ticks not
replayed.
"""
from __future__ import annotations

import random
from dataclasses import dataclass
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .backend import SimBackend, scenario
from .network import store_and_forward_delay
from .vec_engine import BatchPlan, Loop, VecEngine, make_batch_entry
from .workflow import (NetworkCloudlet, StageKind, _normalize_guests,
                       _workflow_batch_build, _workflow_result)

# Stage-kind codes (PAD marks unused padded slots).
PAD, EXEC, SEND, RECV = 0, 1, 2, 3

_STAGE_CODE = {StageKind.EXEC: EXEC, StageKind.SEND: SEND, StageKind.RECV: RECV}


@dataclass(frozen=True)
class _WfStatics:
    """Shape-defining (compile-time) configuration."""
    n_tasks: int
    max_stages: int
    n_guests: int
    max_iters: int
    use_pallas: bool

    @property
    def cascade_rounds(self) -> int:
        # One SEND + one RECV can advance per round; a task can chain at
        # most max_stages non-blocking stages at one instant.
        return self.max_stages + 1


class WorkflowSpec(NamedTuple):
    """One scenario cell's SoA arrays (stack along axis 0 to batch)."""
    kind: Any          # [T, S] i32  PAD/EXEC/SEND/RECV
    slen: Any          # [T, S] f64  EXEC MI
    before: Any        # [T, S] f64  exclusive prefix of earlier EXEC MI
    delay: Any         # [T, S] f64  SEND network delay (closed form)
    send_dst: Any      # [T, S] i32  SEND: destination task index
    send_slot: Any     # [T, S] i32  SEND: matching RECV stage in send_dst
    n_stage: Any       # [T]    i32  stages actually used per task
    pes: Any           # [T]    f64
    guest_of: Any      # [T]    i32
    submit: Any        # [T]    f64  activation arrival times
    gmips: Any         # [G]    f64  granted per-PE MIPS per guest
    gpes: Any          # [G]    f64


class _WfCarry(NamedTuple):
    now: Any           # [] f64 current event time
    t_next: Any        # [] f64 next event (inf ⇒ lane done)
    sidx: Any          # [T] i32 current stage index
    done: Any          # [T] f64 MI executed (Cloudlet.length_so_far)
    arrival: Any       # [T, S] f64 packet arrival time per RECV slot
    finish: Any        # [T] f64 finish times (inf until done)


def _at_stage(arr, sidx):
    """arr[t, sidx[t]] with clamped gather (padded slots are inert)."""
    idx = jnp.clip(sidx, 0, arr.shape[-1] - 1)
    return jnp.take_along_axis(arr, idx[:, None], axis=1)[:, 0]


def _cascade(spec: WorkflowSpec, s: _WfStatics, now, sidx, arrival):
    """Advance all non-blocking stages at time ``now`` to a fixpoint —
    the SoA counterpart of ``NetworkCloudlet._advance_nonblocking``."""
    submitted = spec.submit <= now

    def one_round(_, carry):
        sidx, arrival = carry
        alive = submitted & (sidx < spec.n_stage)
        send_m = alive & (_at_stage(spec.kind, sidx) == SEND)
        # Fire SENDs: scatter arrival time into the peer's RECV slot.
        # Masked lanes target (0, 0) with +inf, a no-op under .min();
        # each RECV slot receives exactly one SEND, so .min == .set.
        dst_t = jnp.where(send_m, _at_stage(spec.send_dst, sidx), 0)
        dst_s = jnp.where(send_m, _at_stage(spec.send_slot, sidx), 0)
        at = jnp.where(send_m, now + _at_stage(spec.delay, sidx), jnp.inf)
        arrival = arrival.at[dst_t, dst_s].min(at)
        sidx = sidx + send_m.astype(sidx.dtype)
        # Advance RECVs whose payload has arrived.
        alive = submitted & (sidx < spec.n_stage)
        recv_m = alive & (_at_stage(spec.kind, sidx) == RECV) \
            & (_at_stage(arrival, sidx) <= now)
        sidx = sidx + recv_m.astype(sidx.dtype)
        return sidx, arrival

    return jax.lax.fori_loop(0, s.cascade_rounds, one_round, (sidx, arrival))


def _wf_build(spec: WorkflowSpec, s: _WfStatics, ops) -> Loop:
    """One scenario cell, start to finish (one event per loop iteration)."""
    granted = spec.gmips * spec.gpes                     # per-guest MIPS pool

    def body(c: _WfCarry, it) -> _WfCarry:
        # 1. Non-blocking stage cascade at the current event time (SENDs
        #    fire, satisfied RECVs unblock — incl. 0-delay co-located sends).
        sidx, arrival = _cascade(spec, s, c.now, c.sidx, c.arrival)
        submitted = spec.submit <= c.now
        # 2. Handler 2 (is_finished): record finish at this tick.
        finish = jnp.where(submitted & (sidx >= spec.n_stage)
                           & jnp.isinf(c.finish), c.now, c.finish)
        # 3. Time-shared allocation (CloudletSchedulerTimeShared semantics):
        #    only EXEC stages consume share (wants_cpu).
        kind_now = _at_stage(spec.kind, sidx)
        active = submitted & (sidx < spec.n_stage) & (kind_now == EXEC)
        req_pes = jax.ops.segment_sum(jnp.where(active, spec.pes, 0.0),
                                      spec.guest_of,
                                      num_segments=s.n_guests)
        denom = jnp.maximum(req_pes, spec.gpes)
        cap = jnp.where(denom > 0, granted / jnp.where(denom > 0, denom, 1.0),
                        0.0)
        alloc = jnp.where(active, cap[spec.guest_of] * spec.pes, 0.0)
        # 4. Next event = min(EXEC finish estimates, future submissions,
        #    in-flight packet arrivals) — Algorithm 1 lines 17-23.
        room = _at_stage(spec.slen, sidx) - (c.done - _at_stage(spec.before,
                                                                sidx))
        runnable = active & (alloc > 0)
        est = jnp.where(
            runnable,
            c.now + jnp.maximum(room, 0.0) / jnp.where(runnable, alloc, 1.0),
            jnp.inf)
        fut = jnp.where(spec.submit > c.now, spec.submit, jnp.inf)
        waiting = submitted & (sidx < spec.n_stage) & (kind_now == RECV)
        wake = jnp.where(waiting & (_at_stage(arrival, sidx) > c.now),
                         _at_stage(arrival, sidx), jnp.inf)
        t_next = ops.min(jnp.concatenate([est, fut, wake]))
        # 5. Handler 1 (update_progress) over the window [now, t_next]:
        #    step = min(span·alloc, room), 1e-9 completion tolerance —
        #    the OO engine's exact arithmetic.
        live = jnp.isfinite(t_next)
        span = jnp.where(live, t_next - c.now, 0.0)
        step = jnp.minimum(span * alloc, room)
        done = jnp.where(active, c.done + step, c.done)
        completed = active & live & (step >= room - 1e-9)
        return _WfCarry(
            now=jnp.where(live, t_next, c.now),
            t_next=t_next,
            sidx=sidx + completed.astype(sidx.dtype),
            done=done,
            arrival=arrival,
            finish=finish)

    zf = jnp.asarray(0.0, spec.slen.dtype)
    init = _WfCarry(
        now=zf, t_next=zf,
        sidx=jnp.zeros((s.n_tasks,), jnp.int32),
        done=jnp.zeros((s.n_tasks,), spec.slen.dtype),
        arrival=jnp.full((s.n_tasks, s.max_stages), jnp.inf, spec.slen.dtype),
        finish=jnp.full((s.n_tasks,), jnp.inf, spec.slen.dtype))
    return Loop(
        init=init,
        cond=lambda c, it: jnp.isfinite(c.t_next) & (it < s.max_iters),
        body=body,
        finalize=lambda c, it: dict(finish=c.finish, done=c.done))


WORKFLOW_ENGINE = VecEngine("workflow_batch", _wf_build)


# ---------------------------------------------------------------------------
# Host-side spec builders (numpy; float arithmetic mirrors the OO engine)
# ---------------------------------------------------------------------------

def _edge_delay(payload_bytes: float, links: int, n_switches: int,
                switch_latency: float, bw: float, ov_src: float,
                ov_dst: float) -> float:
    """Closed-form ``NetworkTopology.transfer_delay`` — same float ops, same
    order (incl. the C4 composed nesting overheads at both endpoints)."""
    switch_lat = 0.0
    for _ in range(n_switches):                  # sum() over equal latencies
        switch_lat += switch_latency
    return store_and_forward_delay(payload_bytes, links, bw, switch_lat,
                                   ov_src + ov_dst)


def _links_between(g_src: int, g_dst: int, host_of_guest, rack_of_host
                   ) -> Tuple[int, int]:
    """(store-and-forward links, switches) between two guests' hosts —
    ``NetworkTopology.path_links``/``switches_on_path`` semantics."""
    hs, hd = host_of_guest[g_src], host_of_guest[g_dst]
    if hs == hd:
        return 0, 0
    if rack_of_host[hs] == rack_of_host[hd]:
        return 2, 1                              # host→ToR→host
    return 4, 3                                  # host→ToR→Agg→ToR→host


def build_spec(dags: Sequence[Sequence[NetworkCloudlet]],
               guest_of_task: Sequence[int],
               submit_of_dag: Sequence[float], *,
               guest_mips: Sequence[float], guest_pes: Sequence[float],
               guest_overhead: Sequence[float], guest_bw: Sequence[float],
               host_of_guest: Sequence[int], rack_of_host: Sequence[int],
               link_bw: float = 1e9, switch_latency: float = 0.0
               ) -> WorkflowSpec:
    """Flatten DAG activations (as ``NetworkCloudlet`` templates, so stage
    layout is identical to what the OO engine executes) into SoA arrays."""
    tasks: List[NetworkCloudlet] = [cl for dag in dags for cl in dag]
    id2idx = {cl.id: i for i, cl in enumerate(tasks)}
    T = len(tasks)
    S = max(len(cl.stages) for cl in tasks)

    kind = np.zeros((T, S), np.int32)
    slen = np.zeros((T, S), np.float64)
    before = np.zeros((T, S), np.float64)
    delay = np.zeros((T, S), np.float64)
    send_dst = np.zeros((T, S), np.int32)
    send_slot = np.zeros((T, S), np.int32)
    n_stage = np.zeros((T,), np.int32)
    pes = np.zeros((T,), np.float64)
    guest_of = np.asarray(guest_of_task, np.int32)
    submit = np.zeros((T,), np.float64)

    ti = 0
    for d, dag in enumerate(dags):
        for cl in dag:
            n_stage[ti] = len(cl.stages)
            pes[ti] = float(cl.pes)
            submit[ti] = float(submit_of_dag[d])
            acc = 0.0
            for si, st in enumerate(cl.stages):
                kind[ti, si] = _STAGE_CODE[st.kind]
                before[ti, si] = acc                 # OO's ordered prefix sum
                if st.kind == StageKind.EXEC:
                    slen[ti, si] = st.length
                    acc += st.length
                elif st.kind == StageKind.SEND:
                    dst = id2idx[st.peer]
                    send_dst[ti, si] = dst
                    # Matching RECV slot in the peer (unique per src task).
                    slot = next(j for j, ps in enumerate(tasks[dst].stages)
                                if ps.kind == StageKind.RECV
                                and ps.peer == cl.id)
                    send_slot[ti, si] = slot
                    gs, gd = guest_of[ti], guest_of[dst]
                    links, n_sw = _links_between(gs, gd, host_of_guest,
                                                 rack_of_host)
                    bw = min(link_bw, guest_bw[gs], guest_bw[gd])
                    delay[ti, si] = _edge_delay(
                        st.payload_bytes, links, n_sw, switch_latency, bw,
                        guest_overhead[gs], guest_overhead[gd])
            ti += 1

    return WorkflowSpec(
        kind=kind, slen=slen, before=before, delay=delay, send_dst=send_dst,
        send_slot=send_slot, n_stage=n_stage, pes=pes, guest_of=guest_of,
        submit=submit, gmips=np.asarray(guest_mips, np.float64),
        gpes=np.asarray(guest_pes, np.float64))


def arrival_times(activations: int, seed: int, rate: Optional[float]
                  ) -> List[float]:
    """The shared Poisson activation stream — the *same*
    ``random.Random(seed)`` draws the OO case study consumes, so vec and OO
    cells see identical arrivals."""
    rng = random.Random(seed)
    t, out = 0.0, []
    for a in range(activations):
        if a > 0 and rate is not None:
            t += rng.expovariate(rate)
        out.append(t)
    return out


def pad_stack(specs: Sequence[WorkflowSpec]) -> WorkflowSpec:
    """Stack per-cell specs into one batched spec (cells must share shapes;
    the case-study grid always does)."""
    return WorkflowSpec(*(np.stack([np.asarray(getattr(sp, f))
                                    for sp in specs])
                          for f in WorkflowSpec._fields))


def _prepare_specs(specs: Sequence[WorkflowSpec], *, use_pallas: bool,
                   max_iters: Optional[int] = None) -> BatchPlan:
    batched = pad_stack(specs)
    T, S = batched.kind.shape[1:]
    G = batched.gmips.shape[1]
    if max_iters is None:
        # Events ≈ submissions + stage completions + packet arrivals; an
        # 8× margin covers contention re-ticks with room to spare.
        max_iters = 8 * T * (S + 1) + 64
    statics = _WfStatics(T, S, G, int(max_iters), bool(use_pallas))
    # Predicted loop length ≈ per-cell live stages + submissions (cells of
    # one grid share padded shapes but not DAG population or arrivals).
    pred = np.asarray(batched.n_stage, np.int64).sum(axis=1) + T
    return BatchPlan(batched, statics, predicted_cost=pred)


simulate_specs = make_batch_entry(
    WORKFLOW_ENGINE, _prepare_specs, backends=(), name="simulate_specs",
    doc="""\
    Run a batch of workflow cells through the sweep execution layer.

    Returns ``finish [B, T]`` (inf = never finished — deadlocked DAG),
    ``done [B, T]`` MI, and per-cell loop ``iterations``; with
    ``with_report=True`` returns ``(stats, SweepReport)``.

    Cells are bucketed by predicted event count, dispatched in bounded
    chunks with donated buffers, and sharded across ``devices`` — all
    bit-identical to the monolithic single-dispatch call (see
    :mod:`repro.core.vec_engine` / :mod:`repro.core.sweep`).
    """)


# ---------------------------------------------------------------------------
# Scenario handlers: the §6 case study + generic batched DAG workflows
# ---------------------------------------------------------------------------

def _case_study_cell(virt: str, placement: str, payload: float,
                     activations: int, overhead_on: bool, seed: int
                     ) -> Tuple[WorkflowSpec, List[float]]:
    """One Figure-5 grid cell as a WorkflowSpec (Table 3 constants)."""
    from .case_study import (ARRIVAL_RATE, BW, L_TASK, MIPS, PLACEMENTS,
                             cell_overhead)
    from .workflow import chain_dag
    ov = cell_overhead(virt, overhead_on)
    h0, h1 = PLACEMENTS[placement]
    arrivals = arrival_times(activations, seed,
                             ARRIVAL_RATE if activations > 1 else None)
    dags = [chain_dag([L_TASK, L_TASK], payload) for _ in range(activations)]
    # T0 on guest 0; T1 co-located for placement I, on guest 1 otherwise.
    g1 = 0 if placement == "I" else 1
    guest_of = [g for _ in range(activations) for g in (0, g1)]
    spec = build_spec(
        dags, guest_of, arrivals,
        guest_mips=[MIPS, MIPS], guest_pes=[1.0, 1.0],
        guest_overhead=[ov, ov], guest_bw=[BW, BW],
        host_of_guest=[h0, h1], rack_of_host=[0, 0, 1, 1],
        link_bw=BW, switch_latency=0.0)
    return spec, arrivals


def run_case_study_vec(*, virt: str = "V", placement: str = "II",
                       payload: Optional[float] = None, activations: int = 1,
                       overhead_on: bool = True, seed: int = 42,
                       use_pallas: bool | str = False,
                       chunk_size: Optional[int] = None,
                       devices=None,
                       with_report: bool = False,
                       **sweep_kw):
    """Vectorized §6 case study — same contract as the OO
    ``run_case_study``.  Scalar parameters return one ``CaseStudyResult``;
    passing a sequence for any of ``virt``/``placement``/``payload``/``seed``
    broadcasts them to a cell grid and returns a list of results computed in
    **one** compiled vmap call (the whole Figure 5 / Table 3 grid at once),
    scheduled by the sweep layer (``chunk_size``/``devices`` plus any
    further sweep controls — ``compact``, ``segment_iters``, ``on_chunk``
    — forwarded to :func:`simulate_specs`;
    ``with_report=True`` additionally returns the ``SweepReport``).
    """
    from .case_study import PAYLOAD_BIG, CaseStudyResult
    if payload is None:
        payload = PAYLOAD_BIG
    grid_in = (virt, placement, payload, seed)
    scalar = not any(isinstance(v, (list, tuple, np.ndarray))
                     for v in grid_in)
    axes = [np.atleast_1d(np.asarray(v, dtype=object)) for v in grid_in]
    B = int(np.broadcast_shapes(*(a.shape for a in axes))[0])
    virts, places, payloads, seeds = (np.broadcast_to(a, (B,)) for a in axes)

    specs, cell_arrivals = [], []
    for b in range(B):
        spec, arr = _case_study_cell(str(virts[b]), str(places[b]),
                                     float(payloads[b]), activations,
                                     overhead_on, int(seeds[b]))
        specs.append(spec)
        cell_arrivals.append(arr)
    out, report = simulate_specs(specs, use_pallas=use_pallas,
                                 chunk_size=chunk_size, devices=devices,
                                 with_report=True, **sweep_kw)

    from .case_study import cell_theoretical
    results = []
    for b in range(B):
        finish = out["finish"][b]
        assert np.all(np.isfinite(finish)), "workflow did not complete"
        makespans = [max(finish[2 * a], finish[2 * a + 1])
                     - cell_arrivals[b][a] for a in range(activations)]
        results.append(CaseStudyResult(
            makespans, cell_theoretical(str(virts[b]), str(places[b]),
                                        float(payloads[b]), overhead_on),
            str(virts[b]), str(places[b]), float(payloads[b])))
    results = results[0] if scalar else results
    return (results, report) if with_report else results


@scenario("case_study", backends=("vec",))
def _case_study_vec(backend: SimBackend, **kw):
    """Vec implementation of the §6 case study (closes the last
    ScenarioUnsupported gap — see ISSUE 2)."""
    return run_case_study_vec(**kw)


# -- generic batched DAG workflows ("workflow_batch" kind) ---------------------

@scenario("workflow_batch", backends=("vec",))
def _workflow_batch_vec(backend: SimBackend, *, nodes, edges,
                        payload: float = 0.0, guest_of, guest_mips,
                        guest_pes=None, guest_overhead=None, guest_bw=None,
                        host_of_guest=None, rack_of_host=None,
                        link_bw: float = 1e9, switch_latency: float = 0.0,
                        activations: int = 1, seed: int = 0,
                        arrival_rate: Optional[float] = None,
                        deadline: Optional[float] = None,
                        use_pallas: bool | str = False,
                        chunk_size: Optional[int] = None,
                        devices=None,
                        with_report: bool = False,
                        **sweep_kw):
    """Batched generic-DAG workflows through the sweep execution layer.

    ``nodes`` are EXEC lengths (MI), ``edges`` are ``(src, dst)`` index
    pairs (≤ one edge per ordered pair), ``guest_of`` places each node on a
    (time-shared) guest.  ``payload`` and ``seed`` broadcast to the batch
    axis.  Returns ``finish [B, T]``, ``makespans [B, activations]``,
    ``missed_deadline [B, T]``, ``iterations [B]``; with
    ``with_report=True`` returns ``(dict, SweepReport)``.
    """
    guest_pes, guest_overhead, guest_bw, host_of_guest, rack_of_host = \
        _normalize_guests(guest_mips, guest_pes, guest_overhead, guest_bw,
                          host_of_guest, rack_of_host, link_bw)
    specs, arrivals, _, B = _workflow_batch_build(
        nodes, edges, payload, guest_of, guest_mips, guest_pes,
        guest_overhead, guest_bw, host_of_guest, rack_of_host, link_bw,
        switch_latency, activations, seed, arrival_rate, deadline)
    out, report = simulate_specs(specs, use_pallas=use_pallas,
                                 chunk_size=chunk_size, devices=devices,
                                 with_report=True, **sweep_kw)
    submit = np.stack([np.asarray(sp.submit) for sp in specs])
    makespans, missed = _workflow_result(out["finish"], arrivals, activations,
                                         len(nodes), submit, deadline)
    res = dict(finish=out["finish"], makespans=makespans,
               missed_deadline=missed, iterations=out["iterations"])
    return (res, report) if with_report else res


@scenario("workflow_batch", backends=("legacy", "oo"))
def _workflow_batch_oo(backend: SimBackend, *, nodes, edges,
                       payload: float = 0.0, guest_of, guest_mips,
                       guest_pes=None, guest_overhead=None, guest_bw=None,
                       host_of_guest=None, rack_of_host=None,
                       link_bw: float = 1e9, switch_latency: float = 0.0,
                       activations: int = 1, seed: int = 0,
                       arrival_rate: Optional[float] = None,
                       deadline: Optional[float] = None,
                       **_ignored) -> Dict[str, np.ndarray]:
    """Reference semantics for ``workflow_batch``: loop the OO event engine
    (:func:`repro.core.workflow._workflow_batch_oo_impl`) over every cell —
    what the vec engine replaces with one vmap call."""
    from .workflow import _workflow_batch_oo_impl
    guest_pes, guest_overhead, guest_bw, host_of_guest, rack_of_host = \
        _normalize_guests(guest_mips, guest_pes, guest_overhead, guest_bw,
                          host_of_guest, rack_of_host, link_bw)
    return _workflow_batch_oo_impl(
        backend, nodes=nodes, edges=edges, payload=payload,
        guest_of=guest_of, guest_mips=guest_mips, guest_pes=guest_pes,
        guest_overhead=guest_overhead, guest_bw=guest_bw,
        host_of_guest=host_of_guest, rack_of_host=rack_of_host,
        link_bw=link_bw, switch_latency=switch_latency,
        activations=activations, seed=seed, arrival_rate=arrival_rate,
        deadline=deadline)
