"""Vectorized Algorithm 1 — the paper's scheduler life-cycle as JAX SoA.

CloudSim's ``CloudletScheduler`` advances each cloudlet with a Python/Java
``for`` loop per scheduler per event; here all guests × all cloudlets advance
in one fused masked-vector pass and "next event" is a masked min reduction
(``repro.kernels.ops``), with the whole simulation (Algorithm 1 lines 1–23,
iterated to completion) inside a single ``lax.while_loop`` — the substrate
conventions live in :mod:`repro.core.vec_engine`.

Semantics exactly match ``CloudletSchedulerTimeShared`` /
``CloudletSchedulerSpaceShared`` (asserted by tests against the OO engine):

  time-shared : per-guest capacity = granted / max(Σ active pes, num_pes),
                every submitted cloudlet runs immediately;
  space-shared: cloudlets admitted FIFO while free PEs remain, each running
                at (granted / num_pes) · pes.

State layout (G guests × C cloudlet slots, padded with zeros):
  length[G,C]   total MI          done[G,C]    MI executed
  pes[G,C]      PEs requested     submit[G,C]  submission time
  finish[G,C]   finish time (inf until done)
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ..kernels.ops import masked_min
from .backend import SimBackend, scenario
from .vec_engine import BatchPlan, Loop, VecEngine, make_batch_entry, x64

INF = jnp.inf


class VecSchedState(NamedTuple):
    length: jax.Array      # [G, C] total MI per cloudlet (0 => empty slot)
    done: jax.Array        # [G, C] MI executed so far
    pes: jax.Array         # [G, C] PEs requested
    submit: jax.Array      # [G, C] submission times
    finish: jax.Array      # [G, C] finish times (inf = not finished)
    now: jax.Array         # [] current simulation time


def make_state(length, pes, submit) -> VecSchedState:
    length = jnp.asarray(length, jnp.float64)
    return VecSchedState(
        length=length,
        done=jnp.zeros_like(length),
        pes=jnp.asarray(pes, jnp.float64),
        submit=jnp.asarray(submit, jnp.float64),
        finish=jnp.full_like(length, INF),
        now=jnp.asarray(0.0, jnp.float64),
    )


def _alloc_mips(state: VecSchedState, guest_mips, guest_pes, mode: str):
    """Per-cloudlet allocated MIPS under the given sharing mode. [G, C]."""
    arrived = state.submit <= state.now
    unfinished = state.done < state.length - 1e-9
    valid = state.length > 0
    active = arrived & unfinished & valid                      # [G, C]
    if mode == "time":
        req_pes = jnp.sum(jnp.where(active, state.pes, 0.0), axis=1)    # [G]
        denom = jnp.maximum(req_pes, guest_pes)
        capacity = jnp.where(denom > 0, guest_mips * guest_pes / denom, 0.0)
        return jnp.where(active, capacity[:, None] * state.pes, 0.0), active
    elif mode == "space":
        # FIFO admission by slot order: run while cumulative PEs fit.
        cum = jnp.cumsum(jnp.where(active, state.pes, 0.0), axis=1)
        admitted = active & (cum <= guest_pes[:, None] + 1e-9)
        return jnp.where(admitted, guest_mips[:, None] * state.pes, 0.0), admitted
    raise ValueError(mode)


def _next_event_time(state: VecSchedState, alloc, use_pallas: bool) -> jax.Array:
    """min over (est. finish of running cloudlets, future submissions) —
    through :func:`repro.kernels.ops.masked_min` (exact minima on both the
    jnp and Pallas paths, so results are bit-identical)."""
    remaining = jnp.maximum(state.length - state.done, 0.0)
    est = jnp.where(alloc > 0, state.now + remaining / jnp.maximum(alloc, 1e-30), INF)
    future = jnp.where(state.submit > state.now, state.submit, INF)
    return masked_min(jnp.concatenate([est.reshape(-1), future.reshape(-1)]),
                      use_pallas=use_pallas)


@functools.partial(jax.jit, static_argnames=("mode", "use_pallas"))
def step(state: VecSchedState, guest_mips, guest_pes, mode: str,
         use_pallas: bool = False) -> Tuple[VecSchedState, jax.Array]:
    """One Algorithm-1 pass for ALL guests: advance to the next event.

    Returns (new_state, next_time). next_time == inf ⇒ simulation complete.
    """
    alloc, _ = _alloc_mips(state, guest_mips, guest_pes, mode)
    t_next = _next_event_time(state, alloc, use_pallas)           # lines 17-23
    span = jnp.where(jnp.isfinite(t_next), t_next - state.now, 0.0)
    done = jnp.minimum(state.done + span * alloc, state.length)   # lines 2-5
    newly = (done >= state.length - 1e-9) & (state.done < state.length - 1e-9) \
            & (state.length > 0)                                  # lines 6-9
    finish = jnp.where(newly, t_next, state.finish)
    new = state._replace(done=done, finish=finish,
                         now=jnp.where(jnp.isfinite(t_next), t_next, state.now))
    return new, t_next


@functools.partial(jax.jit, static_argnames=("mode", "use_pallas"))
def simulate(state: VecSchedState, guest_mips, guest_pes, mode: str,
             use_pallas: bool = False) -> VecSchedState:
    """Run Algorithm 1 to completion inside one lax.while_loop."""

    def cond(carry):
        st, t = carry
        return jnp.isfinite(t)

    def body(carry):
        st, _ = carry
        return step(st, guest_mips, guest_pes, mode, use_pallas)

    st, t0 = step(state, guest_mips, guest_pes, mode, use_pallas)
    st, _ = jax.lax.while_loop(cond, body, (st, t0))
    return st


def _canonical_order(submit):
    """Space-shared FIFO is defined by *arrival* order: canonicalize slot
    order to (submit time, slot index) per guest; returns (order, inverse)."""
    import numpy as np
    order = np.argsort(submit + np.arange(submit.shape[-1]) * 1e-12, axis=-1,
                       kind="stable")
    return order, np.argsort(order, axis=-1, kind="stable")


def simulate_batch(length, pes, submit, guest_mips, guest_pes,
                   mode: str = "time", *, use_pallas: bool | str = False):
    """Convenience wrapper: returns finish times [G, C] (inf for empty slots).

    Runs under x64 so event times match the OO engine's doubles bit-for-bit
    (enabled locally — the model stack elsewhere stays on default f32/bf16).
    All guests share one global event clock, exactly like the OO kernel —
    for a *batch of independent scheduler problems* (cells that may be
    chunked/sharded without changing a bit) use :func:`simulate_cells`.
    """
    import numpy as np
    from ..kernels.ops import resolve_use_pallas
    use_pallas = resolve_use_pallas(use_pallas)
    length = np.asarray(length, np.float64)
    pes = np.asarray(pes, np.float64)
    submit = np.asarray(submit, np.float64)
    order, inv = _canonical_order(submit)
    g_idx = np.arange(length.shape[0])[:, None]
    with x64():
        guest_mips = jnp.asarray(guest_mips, jnp.float64)
        guest_pes = jnp.asarray(guest_pes, jnp.float64)
        st = simulate(make_state(length[g_idx, order], pes[g_idx, order],
                                 submit[g_idx, order]),
                      guest_mips, guest_pes, mode, use_pallas)
        return np.asarray(st.finish)[g_idx, inv]


# -- multi-cell batched entry (a VecEngine definition) -------------------------

class _CellStatics(NamedTuple):
    mode: str
    use_pallas: bool


def _cells_build(params, statics: _CellStatics, ops) -> Loop:
    """One complete [G, C] scheduler problem per cell, on its own event
    clock (cells never interact — chunking/sharding the cell axis is
    bit-identical to the monolithic dispatch, unlike guests *within* a
    cell, which share the global clock)."""
    length, pes, submit, gmips, gpes = params
    run = functools.partial(step, guest_mips=gmips, guest_pes=gpes,
                            mode=statics.mode, use_pallas=statics.use_pallas)
    return Loop(
        init=run(make_state(length, pes, submit)),
        cond=lambda c, it: jnp.isfinite(c[1]),
        body=lambda c, it: run(c[0]),
        # One step ran before the loop: count it in the iteration total.
        finalize=lambda c, it: dict(finish=c[0].finish, iterations=it + 1))


CELLS_ENGINE = VecEngine("cloudlet_batch", _cells_build)


def _prepare_cells(length, pes, submit, guest_mips, guest_pes,
                   mode: str = "time", *, use_pallas: bool) -> BatchPlan:
    import numpy as np
    length = np.asarray(length, np.float64)
    pes = np.asarray(pes, np.float64)
    submit = np.asarray(submit, np.float64)
    order, inv = _canonical_order(submit)
    params = (np.take_along_axis(length, order, -1),
              np.take_along_axis(pes, order, -1),
              np.take_along_axis(submit, order, -1),
              np.asarray(guest_mips, np.float64),
              np.asarray(guest_pes, np.float64))
    return BatchPlan(
        params, _CellStatics(mode, bool(use_pallas)),
        # Loop length ≈ events ≈ live cloudlets (+ their submissions).
        predicted_cost=np.count_nonzero(length > 0, axis=(1, 2)) + 1,
        finalize=lambda out: np.take_along_axis(out["finish"], inv, -1))


simulate_cells = make_batch_entry(
    CELLS_ENGINE, _prepare_cells, backends=(), name="simulate_cells", doc="""\
    Batch of independent scheduler cells through the sweep layer.

    ``length``/``pes``/``submit`` are ``[B, G, C]``; ``guest_mips``/
    ``guest_pes`` are ``[B, G]``.  Every cell advances on its own event
    clock (the [G, C] semantics within one cell are exactly
    :func:`simulate_batch`'s).  Returns finish times ``[B, G, C]``; with
    ``with_report=True`` returns ``(finish, SweepReport)``.  Cells are
    bucketed by live-cloudlet count, chunked with donated buffers, and
    sharded across devices — bit-identical to the monolithic dispatch.
    """)


# -- backend substrate handlers ------------------------------------------------

@scenario("cloudlet_batch", backends=("vec",))
def _cloudlet_batch_vec(backend: SimBackend, *, length, pes, submit,
                        guest_mips, guest_pes, mode: str = "time",
                        use_pallas: bool | str = False, **sweep_kw):
    """Finish times via the compiled SoA path: ``[G, C]`` inputs run the
    single-problem global-clock simulator; ``[B, G, C]`` inputs run a batch
    of independent cells through the sweep layer (``chunk_size`` /
    ``devices`` / ``with_report`` accepted)."""
    import numpy as np
    if np.asarray(length).ndim == 3:
        return simulate_cells(length, pes, submit, guest_mips, guest_pes,
                              mode, use_pallas=use_pallas, **sweep_kw)
    return simulate_batch(length, pes, submit, guest_mips, guest_pes, mode,
                          use_pallas=use_pallas)


@scenario("cloudlet_batch", backends=("legacy", "oo"))
def _cloudlet_batch_oo(backend: SimBackend, *, length, pes, submit,
                       guest_mips, guest_pes, mode: str = "time",
                       use_pallas: bool = False):
    """Reference semantics (:func:`repro.core.scheduler
    ._cloudlet_batch_oo_impl`): the OO event engine, per cell.  Sweep
    controls are deliberately *not* accepted — ``backend.run_sweep``'s
    contract is a ``TypeError``, not a silently-dropped report."""
    from .scheduler import _cloudlet_batch_oo_impl
    return _cloudlet_batch_oo_impl(backend, length=length, pes=pes,
                                   submit=submit, guest_mips=guest_mips,
                                   guest_pes=guest_pes, mode=mode)
