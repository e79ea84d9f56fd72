"""Plain reference for ``llmserve_batch``: geo-distributed, pipelined LLM
serving after Helix (arXiv:2406.01566), stated once more in numpy.  It
imports nothing of the program under test: request streams, the cluster,
the WAN and every routing table are rebuilt here from the cells' seeds,
placements and axes.

Semantics of one cell:

* the deployment lists every machine's prompt and decode token-layers
  per second and KV capacity in tokens (``machines``); machines sit in
  contiguous, equal region blocks in that order; regions lie on a ring,
  neighbours one WAN link apart and the rest two, each link adding its
  latency plus payload bits over bandwidth;
* the request stream: the offline share arrives at t = 0, the online
  requests after it with uniform gaps in [0, 2 * mean_gap_s); each request
  has a uniform source region and uniform integer prompt and decode token
  counts (numpy ``PCG64`` from the cell's seed);
* requests are routed one by one in submission order.  For every pipeline
  that is online and whose smallest KV capacity holds the request, the
  store-and-forward relay ``depart(s) = max(free(s), depart(s-1) + hop(s))
  + svc(s)`` gives its finish after the egress; the request goes to the
  first pipeline with the least ``finish + bias``, where the bias is the
  locality-weighted WAN time plus a KV-pressure penalty.  The chosen
  pipeline's stages stay busy until their departures.  A request that no
  pipeline can take is dropped.

``dtype=np.float32`` computes the tables and the routing in single
precision: the control that a correct check has to reject.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

IN_BYTES_PER_TOKEN = 2048.0
ACT_BYTES_PER_TOKEN = 16384.0
OUT_BYTES_PER_TOKEN = 2048.0
FIRST_TOKEN_BYTES = 2048.0
# Inputs that take one value per cell; the rest are the deployment's.
CELL_KEYS = ("seeds", "placement", "mean_gap_s", "offline_region")


def workload(seed: int, n_requests: int, n_regions: int, mean_gap_s: float,
             offline_frac: float, prompt_tokens, decode_tokens):
    n_offline = int(round(float(offline_frac) * n_requests))
    rng = np.random.Generator(np.random.PCG64(int(seed)))
    submit = np.zeros(n_requests, np.float64)
    n_online = n_requests - n_offline
    if n_online > 1:
        submit[n_offline + 1:] = np.cumsum(
            rng.uniform(0.0, 2.0 * float(mean_gap_s), n_online - 1))
    src = rng.integers(0, n_regions, n_requests, np.int32)
    prompt = rng.integers(*prompt_tokens, n_requests, np.int64)
    decode = rng.integers(*decode_tokens, n_requests, np.int64)
    return submit, src, prompt, decode


def _wan(n_regions: int, link_bw: float, hop_latency_s: float):
    r = np.arange(n_regions)
    ring = np.minimum(np.abs(r[:, None] - r[None, :]),
                      n_regions - np.abs(r[:, None] - r[None, :]))
    links = np.where(ring == 0, 0, np.where(ring == 1, 1, 2))
    latency = links * float(hop_latency_s)

    def delay(src, dst, payload):
        n = links[src, dst]
        return np.where(n == 0, 0.0,
                        n * (payload * 8.0 / float(link_bw)) + latency[src, dst])
    return delay


def _cell(seed, placement, mean_gap_s, offline_region, p):
    """Routing of one cell: per-request pipeline, finish, TTFT, and the
    tables the summary needs."""
    f = np.dtype(p["dtype"]).type
    n_regions = p["n_regions"]
    prompt_tls = np.asarray(p["machines"]["prompt_tls"], np.float64)
    decode_tls = np.asarray(p["machines"]["decode_tls"], np.float64)
    kv_tokens = np.asarray(p["machines"]["kv_tokens"], np.int64)
    n_machines = len(prompt_tls)
    region = np.asarray([m * n_regions // n_machines
                         for m in range(n_machines)], np.int64)
    delay = _wan(n_regions, p["link_bw"], p["hop_latency_s"])
    submit, src, prompt, decode = workload(
        seed, p["n_requests"], n_regions, mean_gap_s, p["offline_frac"],
        p["prompt_tokens"], p["decode_tokens"])
    pl = np.asarray(placement, np.int64)                  # [P, S]
    n_pipes, n_stages = pl.shape
    p_tok, d_tok = prompt.astype(np.float64), decode.astype(np.float64)
    layers = float(p["n_layers"]) / float(n_stages)
    prompt_svc = p_tok[:, None, None] * layers / prompt_tls[pl][None]
    svc = prompt_svc + d_tok[:, None, None] * layers / decode_tls[pl][None]
    mreg = region[pl]                                     # [P, S]
    hop = np.zeros((len(submit), n_pipes, n_stages), np.float64)
    hop[:, :, 0] = delay(src[:, None], mreg[None, :, 0],
                         (p_tok * IN_BYTES_PER_TOKEN)[:, None])
    for s in range(1, n_stages):
        hop[:, :, s] = delay(mreg[None, :, s - 1], mreg[None, :, s],
                             (p_tok * ACT_BYTES_PER_TOKEN)[:, None])
    tail = delay(mreg[None, :, -1], src[:, None],
                 (d_tok * OUT_BYTES_PER_TOKEN)[:, None])
    first_extra = prompt_svc[:, :, -1] + delay(mreg[None, :, -1],
                                               src[:, None], FIRST_TOKEN_BYTES)
    wan = hop.sum(axis=2) + tail
    kv_need = prompt + decode
    pipe_kv = kv_tokens[pl].min(axis=1)
    bias = ((float(p["locality_weight"]) - 1.0) * wan
            + float(p["kv_penalty_s"])
            * (kv_need.astype(np.float64)[:, None]
               / pipe_kv.astype(np.float64)[None, :]))
    eligible = ((kv_need[:, None] <= pipe_kv[None, :])
                & np.all(mreg != int(offline_region), axis=1)[None, :])

    sub, hop_f, svc_f = submit.astype(f), hop.astype(f), svc.astype(f)
    tail_f, bias_f, fx_f = tail.astype(f), bias.astype(f), first_extra.astype(f)
    n = len(submit)
    free = np.zeros((n_pipes, n_stages), f)
    kv_used = np.zeros((n_pipes, n_stages), np.int64)
    dst = np.full(n, -1, np.int64)
    finish = np.full(n, np.inf, f)
    ttft = np.full(n, np.inf, f)
    for j in range(n):
        d = np.full(n_pipes, sub[j], f)
        deps = np.empty((n_pipes, n_stages), f)
        for s in range(n_stages):
            start_last = np.maximum(free[:, s], d + hop_f[j, :, s])
            d = start_last + svc_f[j, :, s]
            deps[:, s] = d
        fin = d + tail_f[j]
        score = np.where(eligible[j], fin + bias_f[j], np.inf)
        if not eligible[j].any():
            continue
        k = int(np.argmin(score))
        free[k] = deps[k]
        kv_used[k] += kv_need[j]
        dst[j], finish[j] = k, fin[k]
        ttft[j] = start_last[k] + fx_f[j, k]
    return dict(dst=dst, finish=finish.astype(np.float64),
                ttft=ttft.astype(np.float64), kv_used=kv_used,
                submit=submit, decode=decode, svc=svc, wan=wan, placement=pl)


def simulate(cells: Dict[str, np.ndarray], *, machines: Dict[str, list],
             n_machines: int, n_regions: int, n_stages: int, n_requests: int,
             decode_tokens, prompt_tokens, n_layers: int, offline_frac: float,
             slo_ttft_s: float, kv_penalty_s: float, link_bw: float,
             hop_latency_s: float, locality_weight: float,
             dtype=np.float64) -> Dict[str, np.ndarray]:
    """Every output of ``llmserve_batch`` for a batch of cells.

    ``cells`` holds per-cell ``seeds``, ``placement`` ([B, P, S]),
    ``mean_gap_s`` and ``offline_region``."""
    if any(len(v) != n_machines for v in machines.values()):
        raise ValueError(f"machines must list {n_machines} machines")
    p = dict(machines=machines, n_regions=int(n_regions),
             n_requests=int(n_requests), n_layers=n_layers,
             offline_frac=offline_frac, prompt_tokens=tuple(prompt_tokens),
             decode_tokens=tuple(decode_tokens), kv_penalty_s=kv_penalty_s,
             link_bw=link_bw, hop_latency_s=hop_latency_s,
             locality_weight=locality_weight, dtype=dtype)
    rows = [_cell(s, pl, g, o, p) for s, pl, g, o in zip(
        cells["seeds"], cells["placement"], cells["mean_gap_s"],
        cells["offline_region"])]
    dst = np.stack([r["dst"] for r in rows])
    finish = np.stack([r["finish"] for r in rows])
    ttft = np.stack([r["ttft"] for r in rows])
    kv_used = np.stack([r["kv_used"] for r in rows])
    submit = np.stack([r["submit"] for r in rows])
    decode = np.stack([r["decode"] for r in rows])
    b, n = dst.shape
    n_pipes = kv_used.shape[1]
    served_m = dst >= 0
    served = served_m.sum(axis=-1)
    makespan = np.max(np.where(served_m, finish, 0.0), axis=-1)
    lat_total = np.sum(np.where(served_m, finish - submit, 0.0), axis=-1)
    denom = np.maximum(served, 1)
    ttft_total = np.sum(np.where(served_m, ttft, 0.0), axis=-1)
    busy = np.zeros((b, n_machines), np.float64)
    kv_m = np.zeros((b, n_machines), np.int64)
    wan_total = np.zeros(b, np.float64)
    picked = np.clip(dst, 0, None)
    for i, r in enumerate(rows):
        j = np.flatnonzero(served_m[i])
        np.add.at(busy[i], r["placement"][picked[i, j]].ravel(),
                  r["svc"][j, picked[i, j]].ravel())
        np.add.at(kv_m[i], r["placement"].ravel(), kv_used[i].ravel())
        wan_total[i] = r["wan"][j, picked[i, j]].sum()
    span = np.maximum(makespan, 1e-300)[:, None]
    return dict(
        dst=dst, finish=finish, ttft=ttft, kv_used=kv_used,
        served=served, dropped=n - served, makespan=makespan,
        latency_total_s=lat_total,
        latency_mean_s=np.where(served > 0, lat_total / denom, 0.0),
        ttft_mean_s=np.where(served > 0, ttft_total / denom, 0.0),
        slo_violations=np.sum(served_m & (ttft > slo_ttft_s), axis=-1),
        tokens_out=np.sum(np.where(served_m, decode, 0), axis=-1),
        pipe_requests=np.sum(dst[:, :, None] == np.arange(n_pipes), axis=1),
        machine_busy_s=busy, kv_assigned_tokens=kv_m,
        wan_delay_total_s=wan_total,
        utilization=np.where(makespan[:, None] > 0, busy / span, 0.0),
        busiest_machine=np.argmax(busy, axis=-1),
        iterations=np.full(b, n, np.int64))


def events(cells: Dict[str, np.ndarray], *, n_requests: int,
           **_) -> np.ndarray:
    """Loop iterations each cell runs: one per request."""
    return np.full(len(cells["seeds"]), int(n_requests), np.int64)
