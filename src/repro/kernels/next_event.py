"""Fused masked min/argmin "next event" reduction — Pallas kernel.

The vectorized engines (``core.vec_scheduler``, ``core.vec_cluster``) replace
the OO kernel's heap pop with a reduction over structure-of-arrays candidate
event times: the next event is the minimum finite time, and (where a policy
needs the *which*, e.g. "which node's failure interrupts this step") its
argmin.  XLA emits two separate reduction loops for ``min`` + ``argmin``;
this kernel fuses them into one pass over VMEM tiles with running
(value, index) scratch accumulators — the same revisit-and-accumulate
schedule as the flash-attention kernel, degenerated to a 0-d reduction.

Shapes: input ``[R, M]`` (R independent reductions — guests, batch lanes),
outputs ``[R]`` min values and ``[R]`` int32 argmins (first occurrence on
ties, matching ``jnp.argmin``).  Masked-out / padded slots are ``+inf``; an
all-inf row returns ``(inf, 0)`` exactly like ``jnp.argmin``.

Tiling: each program reduces a ``(rows, blk)`` tile; the grid's minor axis
walks the M tiles sequentially so the per-row ``[rows, 1]`` accumulators
carry across tiles.  The TPU lowering takes a tile whose last two
dimensions are multiples of 8 and 128 or the whole array's, so ``blk`` is
the whole M when M fits one block and a multiple of 128 otherwise, and
``rows`` is the whole R when R fits one tile and a multiple of 8 otherwise.
``rows`` targets ~``block`` elements per program: many rows when M is small
(the common sweep shape, R ≫ M, where one-row programs would waste nearly
every vector lane).

The TPU lowering reduces float32 only; f64 runs in interpret mode (CPU
tests of the x64 bit-exact engines).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK = 512


def _next_event_kernel(t_ref, vmin_ref, imin_ref, *, block: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        vmin_ref[...] = jnp.full(vmin_ref.shape, jnp.inf, vmin_ref.dtype)
        imin_ref[...] = jnp.zeros(imin_ref.shape, jnp.int32)

    t = t_ref[...]                                    # [rows, block]
    bmin = jnp.min(t, axis=1, keepdims=True)          # [rows, 1]
    # First occurrence of the minimum as the least column holding it: the
    # TPU's own argmin does not keep jnp.argmin's tie rule.  int32
    # throughout, even under x64 — all the TPU lowering takes.
    cols = jax.lax.broadcasted_iota(jnp.int32, t.shape, 1)
    barg = jnp.min(jnp.where(t == bmin, cols, jnp.int32(block)), axis=1,
                   keepdims=True)
    bidx = j * block + barg
    cur = vmin_ref[...]
    better = bmin < cur                # strict ⇒ earliest block wins ties
    imin_ref[...] = jnp.where(better, bidx, imin_ref[...])
    vmin_ref[...] = jnp.where(better, bmin, cur)


def _auto_rows(r: int, blk: int, block: int) -> int:
    """Rows per program tile: target ~``block`` elements of work per
    program — ``block // M`` rows for small M, so wide sweeps don't run
    one near-empty program per row — as a multiple of 8, or all of R when
    R fits one tile."""
    return _legal_rows(r, block // max(blk, 1))


def _legal_rows(r: int, rows: int) -> int:
    """``rows`` rounded up to the TPU's sublane multiple (8), or all of R."""
    rows = -(-max(int(rows), 1) // 8) * 8
    return max(r, 1) if rows >= r else rows


def _row_block(i, j):
    # An int32 zero: under x64 a bare 0 would make the index map return
    # an i64, which the TPU lowering cannot legalize.
    return i, jnp.int32(0)


def next_event(times: jax.Array, mask: jax.Array | None = None, *,
               block: int = DEFAULT_BLOCK,
               rows_per_block: int | None = None, interpret: bool = True):
    """Fused masked (min, argmin) over the last axis.

    ``times [..., M]`` (+ optional boolean ``mask``, False ⇒ ignore slot)
    → ``(vmin [...], argmin [...] int32)``.  Equivalent to
    ``(jnp.min(where(mask, t, inf), -1), jnp.argmin(where(mask, t, inf), -1))``
    but as one fused pass.  ``rows_per_block=None`` picks the row tiling
    from the input shape (see :func:`_auto_rows`).
    """
    if not interpret and times.dtype != jnp.float32:
        raise NotImplementedError(
            f"the native next-event kernel reduces float32 only, not "
            f"{times.dtype}: the x64 exact engines run use_pallas=False on "
            f"the {jax.default_backend()!r} backend")
    if mask is not None:
        times = jnp.where(mask, times, jnp.asarray(jnp.inf, times.dtype))
    lead = times.shape[:-1]
    m = times.shape[-1]
    t2 = times.reshape((-1, m))
    r = t2.shape[0]
    # The whole M in one tile, or lane-aligned (multiple of 128) tiles.
    blk = max(m, 1) if m <= block else max(block // 128, 1) * 128
    rows = (_auto_rows(r, blk, block) if rows_per_block is None
            else _legal_rows(r, rows_per_block))
    pad_m = (-m) % blk
    pad_r = (-r) % rows
    if pad_m or pad_r:
        # Row/column padding is +inf: padded columns never win a row's
        # reduction; padded rows reduce to (inf, 0) and are sliced off.
        t2 = jnp.pad(t2, ((0, pad_r), (0, pad_m)),
                     constant_values=jnp.asarray(jnp.inf, times.dtype))
    r_pad = r + pad_r
    vmin, imin = pl.pallas_call(
        functools.partial(_next_event_kernel, block=blk),
        out_shape=(jax.ShapeDtypeStruct((r_pad, 1), times.dtype),
                   jax.ShapeDtypeStruct((r_pad, 1), jnp.int32)),
        grid=(r_pad // rows, t2.shape[1] // blk),
        in_specs=[pl.BlockSpec((rows, blk), lambda i, j: (i, j))],
        out_specs=(pl.BlockSpec((rows, 1), _row_block),
                   pl.BlockSpec((rows, 1), _row_block)),
        interpret=interpret,
    )(t2)
    return vmin[:r, 0].reshape(lead), imin[:r, 0].reshape(lead)


def next_event_ref(times: jax.Array, mask: jax.Array | None = None):
    """Pure-jnp oracle for the kernel (two separate reductions)."""
    if mask is not None:
        times = jnp.where(mask, times, jnp.asarray(jnp.inf, times.dtype))
    return jnp.min(times, axis=-1), jnp.argmin(times, axis=-1).astype(jnp.int32)
