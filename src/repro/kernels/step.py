"""Fused Pallas *step* kernels — whole event-loop iterations as one kernel.

The next-event kernel (:mod:`repro.kernels.next_event`) fuses one reduction;
XLA still materializes the *rest* of each ``VecEngine`` loop iteration —
candidate-time gather, winner select via branchless ``where`` over the
(small, static) event-type set, SoA state scatter-update — as separate
fused loops with HBM round-trips between them.  This module fuses the
**entire** ``body`` of a :class:`repro.core.vec_engine.Loop` into a single
``pallas_call``, the same fuse-the-loop-body move that separates
flash-attention from naive attention:

  * :func:`fused_step_body` — one kernel invocation per iteration, for
    engines whose loop is a genuine ``lax.while_loop`` (data-dependent
    ``cond``, e.g. the fleet's wall-clock/steps race).  The surrounding
    ``cond`` stays outside; every op of the body runs inside the kernel.
  * :func:`fused_scan` — the whole static-trip-count loop as **one**
    ``pallas_call`` with ``grid=(trip_count,)``: the state pytree lives in
    VMEM scratch across grid steps (the ``rwkv6_scan`` chunked-recurrence
    pattern — init at step 0, emit at the last step), and per-iteration
    *stream* inputs (demand traces, fault tables) are blocked
    ``(1, ...)``-per-step, which Pallas double-buffers into VMEM ahead of
    the compute on real hardware — the HBM→VMEM prefetch for large tables.

An engine opts in declaratively: its ``build`` returns the loop with a
:class:`repro.core.vec_engine.StepSpec` in ``Loop.step_kernel`` and derives
its jnp ``body`` from the *same* step function via
:func:`repro.core.vec_engine.body_from_step` — both paths execute one op
sequence, so bit-exactness vs the jnp path holds by construction (asserted
by ``tests/test_step_kernel.py``).  The driver imports this module only
when it takes a fused route, so the plain path never loads Pallas.

Mechanics worth knowing:

  * **Closure conversion** — engine bodies close over traced values
    (pre-drawn schedules, PRNG keys, parameter leaves).  Pallas rejects
    kernels capturing array constants, and ``jax.closure_convert`` only
    hoists *differentiable* consts (uint32 PRNG keys stay baked in), so
    :func:`closure_convert_all` traces with ``jax.make_jaxpr`` — whose
    ``ClosedJaxpr.consts`` holds **every** const — and lifts each into a
    kernel operand.
  * **Scalar padding** — Pallas refs are at least rank 1; 0-d state
    leaves/consts are padded to ``(1,)`` at the call boundary and
    reshaped back inside the kernel and after the call.
  * **Interpret vs native** — on CPU the kernels only run in interpret
    mode (strictly slower than the XLA loop; reached via
    ``use_pallas="force"`` — see ``resolve_use_pallas``).  The TPU
    lowering takes f32 steps built from ops it implements; no engine's
    step is one yet (power carries f64 state; the fleet step uses cumsum,
    sort and in-kernel threefry), so the driver refuses fused routes on a
    native backend.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def closure_convert_all(fun: Callable, *example_args):
    """Like :func:`jax.closure_convert`, but hoists **every** captured
    constant — not just differentiable ones — so the returned function is
    Pallas-kernel-clean.  Returns ``(converted, consts)`` where
    ``converted(*flat_args, *consts)`` replays the traced computation."""
    flat_args, in_tree = jax.tree_util.tree_flatten(example_args)
    closed, out_shape = jax.make_jaxpr(
        lambda *flat: fun(*jax.tree_util.tree_unflatten(in_tree, flat)),
        return_shape=True)(*flat_args)
    otree = jax.tree_util.tree_structure(out_shape)
    n_args = len(flat_args)

    def converted(*args_consts):
        args, cs = args_consts[:n_args], args_consts[n_args:]
        out = jax.core.eval_jaxpr(closed.jaxpr, list(cs), *args)
        return jax.tree_util.tree_unflatten(otree, out)

    return converted, list(closed.consts)


def _pad(a):
    """Rank-≥1 view for the pallas_call boundary (refs can't be 0-d)."""
    a = jnp.asarray(a)
    return a.reshape((1,)) if a.ndim == 0 else a


def _pad_shape(s):
    return (1,) if s == () else tuple(s)


def fused_step_body(spec, *, interpret: bool = True
                    ) -> Callable[[Any, Any], Any]:
    """One whole loop iteration as a single ``pallas_call`` —
    drop-in replacement for :func:`body_from_step`'s jnp body inside the
    driver's ``lax.while_loop`` (the ``cond`` stays outside as jnp).

    State leaves, this iteration's stream slices, ``it`` and every
    closed-over constant enter as kernel operands; the body's op sequence
    runs inside the kernel; the new state leaves are the outputs.
    Bit-exact vs the jnp body (min/select/integer ops are exact; float
    ops execute the same sequence on the same values).
    """
    def body(state, it):
        sl = jax.tree_util.tree_map(lambda a: a[it], spec.streams)
        args = (state, sl, it)
        flat, treedef = jax.tree_util.tree_flatten(args)
        shapes = [jnp.shape(x) for x in flat]
        conv, consts = closure_convert_all(
            lambda s, z, i: spec.step(s, z, i), *args)
        out_sd = jax.eval_shape(lambda s, z, i: spec.step(s, z, i), *args)
        out_flat, out_tree = jax.tree_util.tree_flatten(out_sd)
        n_in = len(flat)
        cshapes = [jnp.shape(c) for c in consts]

        def kernel(*refs):
            in_refs, out_refs = refs[:n_in + len(consts)], \
                refs[n_in + len(consts):]
            flat_args = [r[...].reshape(s)
                         for r, s in zip(in_refs[:n_in], shapes)]
            cs = [r[...].reshape(s)
                  for r, s in zip(in_refs[n_in:], cshapes)]
            new = conv(*flat_args, *cs)
            for r, leaf in zip(out_refs, jax.tree_util.tree_leaves(new)):
                r[...] = _pad(leaf)

        outs = pl.pallas_call(
            kernel,
            out_shape=tuple(jax.ShapeDtypeStruct(_pad_shape(o.shape),
                                                 o.dtype)
                            for o in out_flat),
            interpret=interpret,
        )(*[_pad(x) for x in flat], *[_pad(c) for c in consts])
        outs = [o.reshape(s.shape) for o, s in zip(outs, out_flat)]
        return jax.tree_util.tree_unflatten(out_tree, outs)
    return body


def fused_scan(spec, init: Any, trip_count: int, *,
               interpret: bool = True):
    """The whole static-trip-count loop as **one** ``pallas_call``.

    ``grid=(trip_count,)`` walks the iterations sequentially (the grid's
    minor axis, so VMEM scratch carries across steps — the ``rwkv6_scan``
    pattern): step 0 copies the initial state into scratch, every step
    applies ``spec.step`` to the scratch state and this step's stream
    block, and the last step emits scratch to the outputs.  Stream leaves
    use ``(1, ...)`` per-step BlockSpecs — on real hardware Pallas
    double-buffers the next step's block HBM→VMEM while the current one
    computes, which is the whole-table prefetch story for large host/VM
    tables.  Returns the final state pytree; bit-exact vs the equivalent
    ``lax.fori_loop`` over :func:`body_from_step`.
    """
    if trip_count <= 0:
        return init
    flat_init, treedef = jax.tree_util.tree_flatten(init)
    ishapes = [jnp.shape(x) for x in flat_init]
    s_flat, s_tree = jax.tree_util.tree_flatten(spec.streams)
    for a in s_flat:
        if jnp.shape(a)[0] < trip_count:
            raise ValueError(
                f"fused_scan: stream leaf {jnp.shape(a)} shorter than "
                f"trip_count={trip_count}")
    ex_slices = jax.tree_util.tree_unflatten(
        s_tree, [jax.ShapeDtypeStruct(jnp.shape(a)[1:],
                                      jnp.asarray(a).dtype)
                 for a in s_flat])
    conv, consts = closure_convert_all(
        lambda s, z, i: spec.step(s, z, i),
        init, ex_slices, jnp.asarray(0, jnp.int32))
    n_state, n_stream = len(flat_init), len(s_flat)
    cshapes = [jnp.shape(c) for c in consts]
    sshapes = [jnp.shape(a)[1:] for a in s_flat]

    def kernel(*refs):
        it = pl.program_id(0)
        k = n_state + n_stream + len(consts)
        in_refs, out_refs, scratch = refs[:k], refs[k:k + n_state], \
            refs[k + n_state:]

        @pl.when(it == 0)
        def _init():
            for s, r in zip(scratch, in_refs[:n_state]):
                s[...] = r[...]

        st = jax.tree_util.tree_unflatten(
            treedef, [s[...].reshape(sh)
                      for s, sh in zip(scratch, ishapes)])
        sl = jax.tree_util.tree_unflatten(
            s_tree, [r[...].reshape(sh) for r, sh in
                     zip(in_refs[n_state:n_state + n_stream], sshapes)])
        cs = [r[...].reshape(sh)
              for r, sh in zip(in_refs[n_state + n_stream:], cshapes)]
        flat_args = jax.tree_util.tree_leaves((st, sl, it))
        new = conv(*flat_args, *cs)
        for s, leaf in zip(scratch, jax.tree_util.tree_leaves(new)):
            s[...] = _pad(leaf)

        @pl.when(it == trip_count - 1)
        def _emit():
            for o, s in zip(out_refs, scratch):
                o[...] = s[...]

    def full(a):
        a = _pad(a)
        nd = a.ndim
        return pl.BlockSpec(a.shape, lambda i, nd=nd: (0,) * nd)

    def stream_spec(a):
        nd = jnp.asarray(a).ndim
        return pl.BlockSpec((1,) + tuple(jnp.shape(a)[1:]),
                            lambda i, nd=nd: (i,) + (0,) * (nd - 1))

    outs = pl.pallas_call(
        kernel,
        grid=(trip_count,),
        in_specs=[full(a) for a in flat_init]
        + [stream_spec(a) for a in s_flat]
        + [full(c) for c in consts],
        out_specs=tuple(full(a) for a in flat_init),
        out_shape=tuple(jax.ShapeDtypeStruct(_pad_shape(jnp.shape(a)),
                                             jnp.asarray(a).dtype)
                        for a in flat_init),
        scratch_shapes=[pltpu.VMEM(_pad_shape(jnp.shape(a)),
                                   jnp.asarray(a).dtype)
                        for a in flat_init],
        interpret=interpret,
    )(*[_pad(x) for x in flat_init], *s_flat, *[_pad(c) for c in consts])
    outs = [o.reshape(sh) for o, sh in zip(outs, ishapes)]
    return jax.tree_util.tree_unflatten(treedef, outs)
