"""Geo-distributed LLM serving — the ``llmserve_batch`` scenario.

The flagship "millions of users, heavy traffic" workload (ROADMAP item 1),
modeled after Helix (ASPLOS'25): a large model is sharded into pipeline
stages placed on **heterogeneous machines** (A100/L4/T4-like throughput and
KV-cache/VRAM profiles) spread across **geo-distributed regions** joined by
an inter-region WAN (:class:`repro.core.network.InterDCTopology`, the same
closed-form store-and-forward arithmetic as the multi-DC scenario).
Requests arrive from an **online** feeder (a stochastic stream with uniform
inter-arrival gaps) and an **offline** feeder (a batch submitted at t=0),
each carrying a prompt (prefill) and a decode token budget.  A broker
routes every request — at its submission event — to the serving *pipeline*
(one machine per stage) that minimizes its locality-weighted completion
time under a store-and-forward relay model:

  * ingress WAN transfer of the prompt to the first stage's region;
  * per stage, FIFO queueing behind the work already committed to that
    machine, then a prompt+decode service occupancy proportional to the
    stage's layer count over the machine's token-layers/s rates;
  * inter-stage activation transfers between the stage regions;
  * egress of the response back to the request's region.

KV-cache occupancy enters twice: a request is **eligible** for a pipeline
only when its context (prompt + decode tokens) fits the smallest KV
capacity along the pipeline, and a precomputed occupancy-pressure bias
(``kv_penalty_s · kv_need / kv_capacity``) steers load toward pipelines
with VRAM headroom.  A request no pipeline can serve (KV overflow, or a
regional outage via ``offline_region``) is dropped.  TTFT (time to first
token) is the last stage's prompt completion plus a first-token egress.

This module owns everything both backends share — the libm-free workload
feeders (golden-fixture bit-stability), the routing tables (service /
hop / egress / bias matrices, all precomputed host-side so neither backend
multiplies inside its decision loop — no FMA-contraction hazard; built
once per distinct request stream and gathered per cell, see
:class:`LLMServeBatch`), the
routing rule itself, and the host-side summary — plus the OO reference:
a broker entity driving REQUEST_SUBMIT/REQUEST_RETURN events through a
``Simulation``.  The vec implementation (:mod:`repro.core.vec_llmserve`)
is a :class:`~repro.core.vec_engine.VecEngine` over the same tables.

Exactness contract (differential suite + golden fixture): ``oo`` and
``vec`` agree **bit-exactly** on every output — the decision arithmetic is
adds/max/compares over shared precomputed f64 tables, and ties break to
the lowest pipeline index on both paths.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, NamedTuple, Optional

import numpy as np

from .backend import SimBackend, scenario
from .engine import SimEntity, Simulation
from .events import Event, Tag
from .faults import FaultInjector, FaultPlan, RetryPolicy, apply_transient
from .network import InterDCTopology

# Per-machine serving profiles: (class name, prompt token-layers/s,
# decode token-layers/s, KV-cache capacity in tokens).  Helix's cluster
# mixes high-end and commodity GPUs; machines cycle through these classes.
MACHINE_CLASSES = (
    ("A100", 8.0e5, 3.2e4, 160_000),
    ("L4", 2.4e5, 1.2e4, 80_000),
    ("T4", 1.0e5, 6.0e3, 48_000),
)

# WAN payload model (bytes): prompt ingress and response egress scale with
# the token budgets; activations between pipeline stages scale with the
# prompt (hidden-state snapshot); the first generated token is one small
# packet.  All payload arithmetic happens host-side in the tables.
IN_BYTES_PER_TOKEN = 2048.0
ACT_BYTES_PER_TOKEN = 16384.0
OUT_BYTES_PER_TOKEN = 2048.0
FIRST_TOKEN_BYTES = 2048.0


def default_machines(n_machines: int) -> Dict[str, np.ndarray]:
    """Heterogeneous default cluster: machines cycle the Helix-like classes."""
    cls = [MACHINE_CLASSES[m % len(MACHINE_CLASSES)]
           for m in range(n_machines)]
    return dict(
        name=np.asarray([c[0] for c in cls]),
        prompt_tls=np.asarray([c[1] for c in cls], np.float64),
        decode_tls=np.asarray([c[2] for c in cls], np.float64),
        kv_tokens=np.asarray([c[3] for c in cls], np.int64))


def machine_regions(n_machines: int, n_regions: int) -> np.ndarray:
    """Machines sit in contiguous region blocks (Helix's geo clusters)."""
    return np.asarray([m * n_regions // n_machines
                       for m in range(n_machines)], np.int64)


def default_placement(prompt_tls: np.ndarray, n_pipelines: int,
                      n_stages: int) -> np.ndarray:
    """Greedy layout: sort machines by prefill speed (stable, descending)
    and deal them stage-major, so the fastest machines serve the earliest
    stages and every pipeline gets a comparable mix."""
    order = np.argsort(-np.asarray(prompt_tls, np.float64), kind="stable")
    need = n_pipelines * n_stages
    if need > len(order):
        raise ValueError(
            f"placement needs {need} machines "
            f"({n_pipelines} pipelines × {n_stages} stages), "
            f"cluster has {len(order)}")
    return np.asarray(order[:need].reshape(n_stages, n_pipelines).T,
                      np.int64)


def llmserve_workload(seed: int, n_requests: int, n_regions: int, *,
                      mean_gap_s: float, offline_frac: float,
                      prompt_tokens, decode_tokens) -> Dict[str, Any]:
    """One seed's request stream: the offline feeder's batch (all submitted
    at t=0) followed by the online feeder's stream (nondecreasing uniform
    inter-arrival gaps), each request with a uniform source region and
    integer prompt/decode token budgets.

    Drawn vectorized from a ``PCG64`` generator, and deliberately
    libm-free (``uniform``/``integers`` + a ``cumsum`` of gaps — no
    ``exponential``): the stream is the scenario's sole stochastic input,
    and avoiding platform-dependent transcendental rounding keeps the
    committed golden fixtures bit-stable across machines.  Submit times
    are nondecreasing in request order, so both backends process requests
    in the same array order.  Host-side cost matters here: cell prep is
    the vec backend's wall-clock floor (the compiled sweep itself is
    milliseconds), so the feeders must not loop in Python.
    """
    n_offline = int(round(float(offline_frac) * n_requests))
    rng = np.random.Generator(np.random.PCG64(int(seed)))
    submit = np.zeros(n_requests, np.float64)
    n_online = n_requests - n_offline
    if n_online > 1:
        gaps = rng.uniform(0.0, 2.0 * float(mean_gap_s), n_online - 1)
        submit[n_offline + 1:] = np.cumsum(gaps)
    return dict(submit=submit,
                src=rng.integers(0, n_regions, n_requests,
                                 np.int32),
                prompt_tok=rng.integers(*prompt_tokens, n_requests,
                                        np.int64),
                decode_tok=rng.integers(*decode_tokens, n_requests,
                                        np.int64),
                online=np.arange(n_requests) >= n_offline)


class LLMFaults(NamedTuple):
    """Per-cell fault context (present iff the cell was built faulted).

    The vec engine never reads this — its fault view is baked into
    ``LLMServeCell.eligible`` — while the OO broker replays ``windows``
    (machine crash windows; region outages pre-expanded to their member
    machines) live through a :class:`~repro.core.faults.FaultInjector`
    and re-derives the same eligibility from ``base_eligible`` + per-
    machine down counters.  ``perm`` is the stable sort that put the cell
    into effective-submit order (``sorted = orig[perm]``)."""
    windows: tuple            # ((machine, t_start, t_end), ...)
    base_eligible: np.ndarray  # [J, P] bool KV fit ∧ static region mask
    gave_up: np.ndarray       # [J] bool transient retries/budget exhausted
    attempts: np.ndarray      # [J] i64 attempts made per request (>= 1)
    perm: np.ndarray          # [J] i64 stable effective-submit order
    timeout_s: float          # drop when no pipeline finishes inside this


@dataclass(frozen=True)
class LLMServeCell:
    """One cell's routing tables, gathered from its batch's factored build
    (:class:`LLMServeBatch`): the OO broker reads them, and the vec engine
    reads the same doubles packed, so decision bit-identity reduces to both
    backends evaluating the same adds/max/compares over the same doubles.
    Under a :class:`~repro.core.faults.FaultPlan` the per-request rows are
    in effective-submit order and ``eligible`` folds in machine/region
    down windows and given-up requests (the vec fault view); ``fx``
    carries what the OO broker needs to reproduce it from live events."""
    submit: np.ndarray        # [J]       f64 nondecreasing submission times
    src: np.ndarray           # [J]       i32 source region per request
    prompt_tok: np.ndarray    # [J]       i64
    decode_tok: np.ndarray    # [J]       i64
    online: np.ndarray        # [J]       bool online-feeder flag
    kv_need: np.ndarray       # [J]       i64 context tokens (prompt+decode)
    svc: np.ndarray           # [J, P, S] f64 per-stage service occupancy
    hop: np.ndarray           # [J, P, S] f64 arrival WAN delay into stage s
    tail: np.ndarray          # [J, P]    f64 response egress delay
    first_extra: np.ndarray   # [J, P]    f64 last-stage prefill + 1st-token
    wan: np.ndarray           # [J, P]    f64 total WAN time (hops + egress)
    bias: np.ndarray          # [J, P]    f64 locality + KV-pressure penalty
    eligible: np.ndarray      # [J, P]    bool KV fit ∧ all machines online
    placement: np.ndarray     # [P, S]    i64 machine id per pipeline stage
    n_machines: int
    slo_ttft_s: float
    fx: Optional[LLMFaults] = None


def packed_layout(n_pipes: int, n_stages: int) -> Dict[str, slice]:
    """Where each field sits in a cell's column index: ``submit |
    svc[P·S] | hop[P·S] | tail[P] | first_extra[P] | bias[P] |
    eligible[P] | kv_need`` (a row of the vec engine's packed table, up
    to ``kv_need``), then ``wan[P] | base_eligible[P]``, which only the
    host reads."""
    ps = n_pipes * n_stages
    out, lo = {}, 0
    for name, width in (("submit", 1), ("svc", ps), ("hop", ps),
                        ("tail", n_pipes), ("first_extra", n_pipes),
                        ("bias", n_pipes), ("eligible", n_pipes),
                        ("kv_need", 1), ("wan", n_pipes),
                        ("base_eligible", n_pipes)):
        out[name] = slice(lo, lo + width)
        lo += width
    return out


@dataclass(frozen=True)
class LLMServeBatch:
    """A batch's routing tables, factored.

    Every table entry depends on one request and on something small that
    the cell's placement picks: a machine (service; last-stage prefill +
    first token), a region (ingress; egress), a region pair (activation
    hop), or a pipeline's region path, KV capacity, locality weight,
    outage state and, under faults, machines (WAN total, bias,
    eligibility).  So each distinct request stream gets one request
    table ``[J, C]`` holding those columns, and a cell is its stream's
    table gathered by a column index built from its placement and axes
    alone.  Cells that share ``(seed, mean_gap_s)`` (a policy search's
    members over common scenario seeds) share a stream; with every seed
    distinct there is one stream a cell.

    ``batch[i]`` materialises cell ``i``'s :class:`LLMServeCell` (the OO
    broker and tests read it); the vec engine gathers each lane's columns
    on the device; :func:`summarize` reads the request tables in place."""
    table: np.ndarray         # [U, J, C]  f64 request table per stream
    stream: np.ndarray        # [B]        i64 the stream cell b draws
    cols: np.ndarray          # [B, N]     i64 its columns (packed_layout)
    placement: np.ndarray     # [B, P, S]  i64 machine id per stage
    # Per stream [U, J]: submit, src, prompt_tok, decode_tok, online,
    # kv_need; faulted also gave_up, attempts and perm (as LLMFaults).
    requests: Dict[str, np.ndarray]
    n_machines: int
    slo_ttft_s: float
    timeout_s: float
    windows: Optional[tuple] = None   # LLMFaults.windows; None: unfaulted

    def __len__(self) -> int:
        return len(self.stream)

    @property
    def layout(self) -> Dict[str, slice]:
        return packed_layout(*self.placement.shape[1:])

    def __getitem__(self, i: int) -> LLMServeCell:
        row, f = self.table[self.stream[i]][:, self.cols[i]], self.layout
        req = {k: v[self.stream[i]] for k, v in self.requests.items()}
        pl = self.placement[i]
        shape = (row.shape[0],) + pl.shape
        fx = None if self.windows is None else LLMFaults(
            windows=self.windows,
            base_eligible=row[:, f["base_eligible"]] != 0,
            gave_up=req["gave_up"], attempts=req["attempts"],
            perm=req["perm"], timeout_s=self.timeout_s)
        return LLMServeCell(
            submit=req["submit"], src=req["src"],
            prompt_tok=req["prompt_tok"], decode_tok=req["decode_tok"],
            online=req["online"], kv_need=req["kv_need"],
            svc=row[:, f["svc"]].reshape(shape),
            hop=row[:, f["hop"]].reshape(shape), tail=row[:, f["tail"]],
            first_extra=row[:, f["first_extra"]], wan=row[:, f["wan"]],
            bias=row[:, f["bias"]], eligible=row[:, f["eligible"]] != 0,
            placement=pl, n_machines=self.n_machines,
            slo_ttft_s=self.slo_ttft_s, fx=fx)


def _unique_rows(key: np.ndarray):
    """``(first, inverse)`` over the distinct rows of an int ``[N, k]``
    key, taken in lexicographic order: each distinct row's first index,
    and each index's distinct row (``np.unique(key, axis=0)`` gives the
    same, about ten times slower)."""
    order = np.lexsort(key.T[::-1])
    ordered = key[order]
    new = np.ones(len(key), bool)
    np.any(ordered[1:] != ordered[:-1], axis=1, out=new[1:])
    inverse = np.empty(len(key), np.int64)
    inverse[order] = np.cumsum(new) - 1
    return order[new], inverse


def _draw_streams(seeds, gaps, draw, workload, plan: FaultPlan,
                  retry: Optional[RetryPolicy], faulted: bool):
    """Each distinct request stream, drawn once: ``(stream, requests)``,
    the cells' stream ids ``[B]`` and the streams' per-request arrays
    stacked ``[U, J]``.  A stream is keyed on what it depends on: the
    seed and ``mean_gap_s``, or an injected ``workload`` alone, and the
    seed again under faults (transient retries draw from it)."""
    zeros = np.zeros_like(seeds)
    key = np.stack([seeds if workload is None or faulted else zeros,
                    gaps.view(np.int64) if workload is None else zeros],
                   axis=1)
    first, stream = _unique_rows(key)
    streams = []
    for i in first:
        seed = int(seeds[i])
        req = (dict(workload) if workload is not None
               else draw(seed, float(gaps[i])))
        if faulted:
            # Transient failures resolve at the *original* submit times,
            # then a stable sort restores nondecreasing effective-submit
            # order — the shared event order both backends process.
            res = apply_transient(plan, retry, req["submit"],
                                  seed=plan.seed * 1_000_003 + seed)
            perm = np.argsort(res.eff_submit, kind="stable")
            req = {k: v[perm] for k, v in req.items()}
            req.update(submit=res.eff_submit[perm],
                       gave_up=res.gave_up[perm],
                       attempts=res.attempts[perm], perm=perm)
        streams.append(req)
    requests = {k: np.stack([r[k] for r in streams]) for k in streams[0]}
    requests["kv_need"] = requests["prompt_tok"] + requests["decode_tok"]
    return stream, requests


def _request_blocks(requests, machines, regions, topo: InterDCTopology,
                    n_layers: int, n_stages: int, plan: FaultPlan):
    """The request table's columns per machine, region and region pair,
    ``{block: [U, J, width]}``.  Each entry is the IEEE expression, in
    the order, that the cell's own table entry would evaluate."""
    n_streams, n = requests["submit"].shape
    r = np.arange(topo.n_dcs)
    p_tok = requests["prompt_tok"].astype(np.float64)[..., None]  # [U,J,1]
    d_tok = requests["decode_tok"].astype(np.float64)[..., None]
    src = requests["src"][..., None]
    layers = float(n_layers) / float(n_stages)         # layers per stage
    # Service occupancy per (request, machine): prefill then decode at the
    # machine's token-layers/s rates.
    prompt_svc = p_tok * layers / machines["prompt_tls"]          # [U,J,M]
    svc = prompt_svc + d_tok * layers / machines["decode_tls"]
    # WAN legs: ingress into a stage-0 region, activation hops between
    # region pairs, response egress and the first token from a last-stage
    # region.  Active ``link`` fault windows (global for this scenario)
    # stretch every WAN leg of the requests submitted inside them by the
    # severity factor.
    ingress = topo.delay_pairs(src, r, p_tok * IN_BYTES_PER_TOKEN)
    act = topo.delay_pairs(r[:, None], r,
                           (p_tok * ACT_BYTES_PER_TOKEN)[..., None])
    tail = topo.delay_pairs(r, src, d_tok * OUT_BYTES_PER_TOKEN)
    first_delay = topo.delay_pairs(r, src, FIRST_TOKEN_BYTES)
    if plan.has("link"):
        wan_f = plan.degrade_factor(requests["submit"].ravel(), 1
                                    ).reshape(n_streams, n, 1)
        ingress *= wan_f
        act *= wan_f[..., None]
        tail *= wan_f
        first_delay *= wan_f
    return dict(submit=requests["submit"][..., None], svc=svc,
                first_extra=prompt_svc + first_delay[..., regions],
                ingress=ingress, act=act.reshape(n_streams, n, -1),
                tail=tail, kv_need=requests["kv_need"][..., None])


def route_request(free, cell: LLMServeCell, j: int, eligible=None,
                  deadline: float = math.inf):
    """The routing rule, scalar form (the OO broker's inner loop): for each
    eligible pipeline run the store-and-forward relay recurrence

        depart(s) = max(free[p][s], depart(s-1) + hop[s]) + svc[s]

    and pick the first-occurrence argmin of ``finish + bias`` (strict
    ``<``) among pipelines finishing by ``deadline`` (timeout failover).
    The vec engine evaluates the identical expression vectorized
    (``ops.argmin``); both tie-break to the lowest pipeline index.
    ``eligible`` overrides the cell's precomputed row (the faulted OO
    broker passes its live mask).

    Returns ``(pipeline, finish, ttft, per-stage departures)`` —
    ``(-1, inf, inf, None)`` when no pipeline is eligible (dropped).
    """
    n_pipes, n_stages = cell.placement.shape
    elig = cell.eligible[j] if eligible is None else eligible
    best, best_score = -1, np.inf
    best_fin, best_ttft, best_dep = np.inf, np.inf, None
    for p in range(n_pipes):
        if not elig[p]:
            continue
        d = cell.submit[j]
        start_last = d
        dep = []
        for s in range(n_stages):
            a = d + cell.hop[j, p, s]
            start_last = free[p][s] if free[p][s] > a else a
            d = start_last + cell.svc[j, p, s]
            dep.append(d)
        fin = d + cell.tail[j, p]
        if fin > deadline:
            continue
        score = fin + cell.bias[j, p]
        if score < best_score:
            best, best_score, best_fin = p, score, fin
            best_ttft = start_last + cell.first_extra[j, p]
            best_dep = dep
    return best, best_fin, best_ttft, best_dep


def summarize(out: Dict[str, Any], cells: LLMServeBatch) -> Dict[str, Any]:
    """Batch-level serving metrics from per-request ``dst``/``finish``/
    ``ttft`` and the per-slot KV counters — one shared numpy routine so
    every aggregate (guarded means, argmax tie-breaks, busy-time scatters)
    is computed identically for both backends.  Each cell's service and
    WAN times are read from its stream's request table in place."""
    out = dict(out)
    dst = out["dst"] = np.asarray(out["dst"], np.int64)          # [B, J]
    finish = out["finish"] = np.asarray(out["finish"], np.float64)
    ttft = out["ttft"] = np.asarray(out["ttft"], np.float64)
    kv_used = out["kv_used"] = np.asarray(out["kv_used"], np.int64)
    b, n_requests = dst.shape
    n_pipes = kv_used.shape[1]
    submit = cells.requests["submit"][cells.stream]
    decode_tok = cells.requests["decode_tok"][cells.stream]
    served_m = dst >= 0                                          # [B, J]
    served = out["served"] = served_m.sum(axis=-1)
    out["dropped"] = n_requests - served
    out["makespan"] = np.max(np.where(served_m, finish, 0.0), axis=-1)
    lat_total = out["latency_total_s"] = np.sum(
        np.where(served_m, finish - submit, 0.0), axis=-1)
    denom = np.maximum(served, 1)
    out["latency_mean_s"] = np.where(served > 0, lat_total / denom, 0.0)
    ttft_total = np.sum(np.where(served_m, ttft, 0.0), axis=-1)
    out["ttft_mean_s"] = np.where(served > 0, ttft_total / denom, 0.0)
    out["slo_violations"] = np.sum(served_m & (ttft > cells.slo_ttft_s),
                                   axis=-1)
    out["tokens_out"] = np.sum(np.where(served_m, decode_tok, 0), axis=-1)
    p_iota = np.arange(n_pipes)
    out["pipe_requests"] = np.sum(dst[:, :, None] == p_iota, axis=1)
    busy = np.zeros((b, cells.n_machines), np.float64)
    kv_m = np.zeros((b, cells.n_machines), np.int64)
    wan_total = np.zeros(b, np.float64)
    picked = np.clip(dst, 0, None)
    f, pl = cells.layout, cells.placement
    svc_cols = cells.cols[:, f["svc"]].reshape(pl.shape)        # [B, P, S]
    wan_cols = cells.cols[:, f["wan"]]                          # [B, P]
    for i in range(b):
        rows = np.flatnonzero(served_m[i])
        pick = picked[i, rows]
        table = cells.table[cells.stream[i]]
        stage_svc = table[rows[:, None], svc_cols[i, pick]]   # [n, S]
        np.add.at(busy[i], pl[i, pick].ravel(), stage_svc.ravel())
        np.add.at(kv_m[i], pl[i].ravel(), kv_used[i].ravel())
        wan_total[i] = table[rows, wan_cols[i, pick]].sum()
    out["machine_busy_s"] = busy
    out["kv_assigned_tokens"] = kv_m
    out["wan_delay_total_s"] = wan_total
    span = np.maximum(out["makespan"], 1e-300)[:, None]
    out["utilization"] = np.where(out["makespan"][:, None] > 0,
                                  busy / span, 0.0)
    out["busiest_machine"] = np.argmax(busy, axis=-1)
    if cells.windows is not None:
        # Faulted runs: per-request arrays go back to original submission
        # order (the streams were stable-sorted by effective submit), and
        # the summary gains the effective submits + retry counts.
        inv = np.argsort(cells.requests["perm"], axis=-1)[cells.stream]
        for k in ("dst", "finish", "ttft"):
            out[k] = np.take_along_axis(out[k], inv, axis=-1)
        out["submit"] = np.take_along_axis(submit, inv, axis=-1)
        out["retries"] = np.sum(cells.requests["attempts"] - 1,
                                axis=-1)[cells.stream]
    return out


def _fault_view(plan: FaultPlan, submit: np.ndarray, regions: np.ndarray,
                n_regions: int):
    """``(down, windows)``: which machines are down at each request's
    submit (``[U, J, M]`` bool), and the plan's machine crash windows
    with region outages expanded to member machines (the OO broker's
    live view).  Both evaluate the same half-open windows."""
    down = plan.down_mask("node", submit.ravel(), len(regions))
    if plan.has("region"):
        down |= plan.down_mask("region", submit.ravel(),
                               n_regions)[:, regions]
    tgt, ts, te, _ = plan.select("node")
    windows = list(zip(tgt.tolist(), ts.tolist(), te.tolist()))
    r_tgt, r_ts, r_te, _ = plan.select("region")
    for r, a, z in zip(r_tgt.tolist(), r_ts.tolist(), r_te.tolist()):
        windows += [(int(m), a, z) for m in np.flatnonzero(regions == r)]
    return down.reshape(submit.shape + (-1,)), tuple(windows)


def build_cells(*, seeds, n_machines: int = 6, n_regions: int = 3,
                n_stages: int = 2, n_pipelines: Optional[int] = None,
                n_layers: int = 32, n_requests: int = 64, placement=None,
                machines: Optional[Dict[str, np.ndarray]] = None,
                mean_gap_s=1.0, locality_weight=1.0, offline_region=-1,
                offline_frac: float = 0.25, slo_ttft_s: float = 5.0,
                kv_penalty_s: float = 0.5, link_bw: float = 10e9,
                hop_latency_s: float = 0.03, prompt_tokens=(64, 1024),
                decode_tokens=(16, 512),
                fault_plan: Optional[FaultPlan] = None,
                retry: Optional[RetryPolicy] = None,
                timeout_s: float = math.inf, workload=None):
    """Validated routing tables of a batch, ``(LLMServeBatch, B)`` —
    the shared front half of both backends' batch handlers, and ``(None,
    0)`` for an empty batch.

    ``seeds`` and the sweep axes ``mean_gap_s`` / ``locality_weight`` /
    ``offline_region`` broadcast to the batch; ``placement`` is one
    ``[P, S]`` machine-id layout shared by every cell or a batched
    ``[B, P, S]`` (one layout per cell — the placement-search grid).
    An injected ``workload`` replaces the seeded request feeders.
    """
    if workload is not None:
        from .trace import check_workload
        workload, n_requests = check_workload(
            "llmserve_batch", workload,
            dict(submit=np.float64, src=np.int32, prompt_tok=np.int64,
                 decode_tok=np.int64, online=bool), n_targets=n_regions)
        if np.any(workload["prompt_tok"] < 1) or \
                np.any(workload["decode_tok"] < 1):
            raise ValueError("llmserve_batch: workload token budgets "
                             "must be >= 1")
    if n_requests < 1 or n_regions < 1 or n_stages < 1:
        raise ValueError(
            "llmserve_batch needs n_requests ≥ 1, n_regions ≥ 1 and "
            "n_stages ≥ 1")
    if not 0.0 <= float(offline_frac) <= 1.0:
        raise ValueError(f"offline_frac must be in [0, 1]: {offline_frac!r}")
    if not timeout_s > 0:
        raise ValueError(
            f"llmserve_batch: timeout_s must be > 0: {timeout_s}")
    machines = dict(machines) if machines is not None \
        else default_machines(int(n_machines))
    n_machines = len(machines["prompt_tls"])
    for key in ("prompt_tls", "decode_tls"):
        machines[key] = np.asarray(machines[key], np.float64)
        if machines[key].shape != (n_machines,) or \
                not np.all(machines[key] > 0):
            raise ValueError(
                f"machines[{key!r}] must be {n_machines} positive rates")
    machines["kv_tokens"] = np.asarray(machines["kv_tokens"], np.int64)
    regions = machine_regions(n_machines, int(n_regions))
    if fault_plan is not None:
        fault_plan.check_targets("node", n_machines, "machine")
        fault_plan.check_targets("region", int(n_regions), "region")
        if np.any(fault_plan.select("link")[0] >= 0):
            raise ValueError(
                "llmserve_batch link faults are WAN-wide: use target=-1")
    if placement is None:
        n_pipelines = (int(n_pipelines) if n_pipelines
                       else max(1, n_machines // int(n_stages)))
        placement = default_placement(machines["prompt_tls"],
                                      n_pipelines, int(n_stages))
    pl = np.asarray(placement, np.int64)
    if pl.ndim == 2:
        pl = pl[None]
    if pl.ndim != 3 or pl.shape[1] < 1 or pl.shape[2] < 1:
        raise ValueError(
            f"placement must be [P, S] or [B, P, S] machine ids, got "
            f"shape {np.shape(placement)}")
    if pl.min(initial=0) < 0 or pl.max(initial=0) >= n_machines:
        raise ValueError(
            f"placement machine ids must be in [0, {n_machines})")
    flat = np.sort(pl.reshape(pl.shape[0], -1), axis=1)
    if pl.shape[0] and np.any(flat[:, 1:] == flat[:, :-1]):
        raise ValueError("placement must assign distinct machines "
                         "(each machine hosts one pipeline stage)")
    from .vec_engine import broadcast_cells
    seeds, axes, b = broadcast_cells(seeds, dict(
        mean_gap_s=mean_gap_s, locality_weight=locality_weight,
        offline_region=offline_region,
        _placement=np.zeros(pl.shape[0])))
    pl = np.broadcast_to(pl, (b,) + pl.shape[1:]) if b else pl[:0]
    offs = axes["offline_region"].astype(np.int64)
    if b and np.max(offs) >= n_regions:
        raise ValueError(f"offline_region must be < n_regions={n_regions}")
    if b == 0:
        return None, 0
    n_pipes, n_stages = pl.shape[1:]
    topo = InterDCTopology(int(n_regions), link_bw=link_bw,
                           hop_latency_s=hop_latency_s)
    faulted = fault_plan is not None or math.isfinite(timeout_s)
    plan = fault_plan if fault_plan is not None else FaultPlan()
    stream, requests = _draw_streams(
        seeds, np.asarray(axes["mean_gap_s"], np.float64),
        lambda seed, gap: llmserve_workload(
            seed, int(n_requests), int(n_regions), mean_gap_s=gap,
            offline_frac=float(offline_frac), prompt_tokens=prompt_tokens,
            decode_tokens=decode_tokens),
        workload, plan, retry, faulted)
    blocks = _request_blocks(requests, machines, regions, topo,
                             int(n_layers), n_stages, plan)
    at, c0 = {}, 0                       # each block's first column
    for name, block in blocks.items():
        at[name], c0 = c0, c0 + block.shape[-1]
    # Per (cell, pipeline): the columns its stage tables take.
    m_region = regions[pl]                                  # [B, P, S]
    hop_cols = np.concatenate(
        [at["ingress"] + m_region[..., :1],
         at["act"] + m_region[..., :-1] * int(n_regions) + m_region[..., 1:]],
        axis=-1)
    tail_cols = at["tail"] + m_region[..., -1]              # [B, P]
    # What a pipeline's WAN total, bias and eligibility depend on besides
    # the request: its stream, locality weight, outage state, region path
    # and KV capacity (its smallest), and under faults its machines.
    pipe_kv = machines["kv_tokens"][pl].min(axis=2)         # [B, P]
    online = np.all(m_region != offs[:, None, None], axis=2)
    lw = np.asarray(axes["locality_weight"], np.float64)
    per_cell = np.stack([stream, lw.view(np.int64)], axis=-1)   # [B, 2]
    key = np.concatenate(
        [np.broadcast_to(per_cell[:, None], (b, n_pipes, 2)),
         online[..., None], m_region, pipe_kv[..., None]]
        + ([pl] if faulted else []), axis=-1).reshape(b * n_pipes, -1)
    first, combo = _unique_rows(key)
    cu = key[first, 0]                   # each combination's stream, sorted
    rank = np.arange(len(first)) - np.searchsorted(cu, cu)
    width = int(rank.max()) + 1          # combination columns per field
    fields = ("wan", "bias", "eligible") + (
        ("base_eligible",) if faulted else ())
    table = np.empty(requests["submit"].shape + (c0 + len(fields) * width,))
    for name, block in blocks.items():
        table[..., at[name]:at[name] + block.shape[-1]] = block
    j = np.arange(table.shape[1])
    hop = table[cu[:, None, None], j[:, None],
                hop_cols.reshape(-1, n_stages)[first][:, None, :]]
    wan = hop.sum(axis=-1) + table[cu[:, None], j,
                                   tail_cols.ravel()[first][:, None]]
    kv_need = requests["kv_need"][cu]                       # [n, J]
    kv_cap = pipe_kv.ravel()[first][:, None]
    # KV-cache occupancy: hard eligibility against the pipeline's smallest
    # capacity, plus a precomputed pressure bias toward VRAM headroom.
    vals = dict(wan=wan, bias=(
        (lw[first // n_pipes] - 1.0)[:, None] * wan
        + float(kv_penalty_s) * (kv_need.astype(np.float64)
                                 / kv_cap.astype(np.float64))),
        eligible=(kv_need <= kv_cap) & online.ravel()[first][:, None])
    windows = None
    if faulted:
        down, windows = _fault_view(plan, requests["submit"], regions,
                                    int(n_regions))
        pipe_up = ~np.any(down[cu[:, None, None], j[:, None],
                               pl.reshape(-1, n_stages)[first][:, None, :]],
                          axis=-1)
        vals["base_eligible"] = vals["eligible"]
        vals["eligible"] = (vals["base_eligible"] & pipe_up
                            & ~requests["gave_up"][cu])
    combo_cols = {}
    for k, name in enumerate(fields):
        lo = c0 + k * width
        table[cu[:, None], j, (lo + rank)[:, None]] = vals[name]
        combo_cols[name] = (lo + rank[combo]).reshape(b, n_pipes)
    combo_cols.setdefault("base_eligible", combo_cols["eligible"])
    parts = dict(submit=np.full((b, 1), at["submit"]),
                 svc=(at["svc"] + pl).reshape(b, -1),
                 hop=hop_cols.reshape(b, -1), tail=tail_cols,
                 first_extra=at["first_extra"] + pl[..., -1],
                 kv_need=np.full((b, 1), at["kv_need"]), **combo_cols)
    cols = np.concatenate(
        [parts[name] for name in packed_layout(n_pipes, n_stages)], axis=1)
    return LLMServeBatch(
        table=table, stream=stream, cols=cols, placement=pl,
        requests=requests, n_machines=n_machines,
        slo_ttft_s=float(slo_ttft_s), timeout_s=float(timeout_s),
        windows=windows), b


def empty_llmserve_outputs(n_machines: int, faulted: bool = False
                           ) -> Dict[str, np.ndarray]:
    zf, zi = np.empty((0,), np.float64), np.empty((0,), np.int64)
    zjf, zji = np.empty((0, 0), np.float64), np.empty((0, 0), np.int64)
    zm_f = np.empty((0, n_machines), np.float64)
    zm_i = np.empty((0, n_machines), np.int64)
    out = dict(dst=zji, finish=zjf, ttft=zjf,
               kv_used=np.empty((0, 0, 0), np.int64),
               served=zi, dropped=zi, makespan=zf, latency_total_s=zf,
               latency_mean_s=zf, ttft_mean_s=zf, slo_violations=zi,
               tokens_out=zi, pipe_requests=zji, machine_busy_s=zm_f,
               kv_assigned_tokens=zm_i, wan_delay_total_s=zf,
               utilization=zm_f, busiest_machine=zi,
               iterations=np.empty((0,), np.int32))
    if faulted:
        out.update(submit=zjf, retries=zi)
    return out


# -- OO reference: an event-driven broker inside a Simulation ------------------

class LLMServeBroker(SimEntity):
    """Routes each request at its REQUEST_SUBMIT event and collects its
    REQUEST_RETURN — the discrete-event reference the vec engine compiles
    into one ``lax.while_loop``."""

    def __init__(self, sim: Simulation, cell: LLMServeCell):
        super().__init__(sim, "llmserve-broker")
        self.cell = cell
        n_pipes, n_stages = cell.placement.shape
        n = len(cell.submit)
        self.free = [[0.0] * n_stages for _ in range(n_pipes)]
        self.kv_used = np.zeros((n_pipes, n_stages), np.int64)
        self.dst = np.full(n, -1, np.int64)
        self.finish = np.full(n, np.inf)
        self.ttft = np.full(n, np.inf)
        self.completed = 0
        # Under a fault plan eligibility is *live*: machine crash windows
        # arrive as NODE_FAILURE/NODE_RECOVER events (priority -1, so a
        # same-time submit sees the flip), overlapping windows nest via
        # per-machine down counters — the event-driven twin of the
        # precomputed ``cell.eligible`` table the vec engine reads.
        self.down_ct = [0] * cell.n_machines
        if cell.fx is not None and cell.fx.windows:
            FaultInjector(sim, cell.fx.windows, self._apply_fault)

    def _apply_fault(self, target: int, down: bool) -> None:
        delta = 1 if down else -1
        for m in ([target] if target >= 0 else range(len(self.down_ct))):
            self.down_ct[m] += delta

    def start(self) -> None:
        for j, t in enumerate(self.cell.submit):
            self.sim.schedule(float(t), Tag.REQUEST_SUBMIT, self, data=j)

    def process_event(self, ev: Event) -> None:
        c = self.cell
        if ev.tag is Tag.REQUEST_SUBMIT:
            j = ev.data
            fx = c.fx
            if fx is None:
                elig, deadline = None, math.inf
            else:
                if fx.gave_up[j]:
                    return                 # dropped: dst/finish/ttft stay
                elig = [fx.base_eligible[j, p]
                        and not any(self.down_ct[m] for m in c.placement[p])
                        for p in range(len(self.free))]
                deadline = c.submit[j] + fx.timeout_s
            p, fin, ttft, dep = route_request(self.free, c, j, elig,
                                              deadline)
            if p < 0:                      # no eligible pipeline: dropped
                return
            self.free[p] = dep
            self.kv_used[p] += c.kv_need[j]
            self.dst[j] = p
            self.finish[j] = fin
            self.ttft[j] = ttft
            self.sim.schedule(float(fin), Tag.REQUEST_RETURN, self, data=j)
        elif ev.tag is Tag.REQUEST_RETURN:
            self.completed += 1


@scenario("llmserve_batch", backends=("legacy", "oo"))
def _llmserve_batch_oo(backend: SimBackend, *, seeds=(0,),
                       n_machines: int = 6, n_regions: int = 3,
                       n_stages: int = 2, n_pipelines=None,
                       n_layers: int = 32, n_requests: int = 64,
                       placement=None, machines=None, mean_gap_s=1.0,
                       locality_weight=1.0, offline_region=-1,
                       offline_frac: float = 0.25, slo_ttft_s: float = 5.0,
                       kv_penalty_s: float = 0.5, link_bw: float = 10e9,
                       hop_latency_s: float = 0.03,
                       prompt_tokens=(64, 1024), decode_tokens=(16, 512),
                       fault_plan: Optional[FaultPlan] = None,
                       retry: Optional[RetryPolicy] = None,
                       timeout_s: float = np.inf, workload=None,
                       chunk_size: Optional[int] = None,
                       with_report: bool = False, **_ignored):
    """Reference semantics for ``llmserve_batch``: one event-driven broker
    simulation per cell, through the sweep layer's host path (so
    ``run_sweep`` sees a populated report)."""
    from .sweep import run_host_sweep
    from .vec_engine import empty_report
    cells, b = build_cells(
        seeds=seeds, n_machines=n_machines, n_regions=n_regions,
        n_stages=n_stages, n_pipelines=n_pipelines, n_layers=n_layers,
        n_requests=n_requests, placement=placement, machines=machines,
        mean_gap_s=mean_gap_s, locality_weight=locality_weight,
        offline_region=offline_region, offline_frac=offline_frac,
        slo_ttft_s=slo_ttft_s, kv_penalty_s=kv_penalty_s, link_bw=link_bw,
        hop_latency_s=hop_latency_s, prompt_tokens=prompt_tokens,
        decode_tokens=decode_tokens, fault_plan=fault_plan, retry=retry,
        timeout_s=timeout_s, workload=workload)
    if b == 0:
        out = empty_llmserve_outputs(
            n_machines, faulted=fault_plan is not None
            or np.isfinite(timeout_s))
        del out["iterations"]                    # the vec loop's counter
        return (out, empty_report(donate=False)) if with_report else out

    def run_cell(i: int):
        sim = backend.make_simulation()
        broker = LLMServeBroker(sim, cells[i])
        sim.run()
        assert broker.completed == int((broker.dst >= 0).sum()), \
            "llmserve: lost REQUEST_RETURNs"
        return dict(dst=broker.dst, finish=broker.finish,
                    ttft=broker.ttft, kv_used=broker.kv_used)

    rows, report = run_host_sweep(run_cell, b, chunk_size=chunk_size)
    out = summarize({k: np.stack([r[k] for r in rows]) for k in rows[0]},
                    cells)
    return (out, report) if with_report else out
