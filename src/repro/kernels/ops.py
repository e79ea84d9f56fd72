"""Public jit'd wrappers around the Pallas kernels.

The model stack calls these when ``cfg.use_pallas`` (TPU); on CPU they run
in interpret mode (tests) or the models fall back to the XLA reference path.
Layout adapters live here so kernels keep their natural [B, H, S, N] tiling.

The vec simulation engines gate their ``use_pallas`` opt-in through
:func:`resolve_use_pallas`: on CPU the kernels only run in *interpret* mode,
which is strictly slower than the plain XLA reduction (the committed
``BENCH_substrate.json`` once recorded the opt-in costing 3.5×), so the
opt-in auto-falls back to the jnp path with a one-time warning.  Pass
``use_pallas="force"`` to run the interpret-mode kernel anyway (CPU kernel
tests).  Where Pallas lowers natively the opt-in never falls back: a route
that cannot lower there raises when it is traced, before anything runs.
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass

import jax
import jax.numpy as jnp

_PALLAS_BACKENDS = ("tpu", "gpu")
# Backends we have already warned about falling back on — per backend, so
# a CPU fallback warning in a long session doesn't suppress a later,
# genuinely different warning after the process switches default backend
# (e.g. tests flipping JAX_PLATFORMS, a host driving mixed clients).
_warned_pallas_fallback: set = set()


def reset_pallas_warning() -> None:
    """Test helper: forget which backends the fallback warning fired for,
    so the next :func:`resolve_use_pallas` fallback warns again."""
    _warned_pallas_fallback.clear()


def pallas_native() -> bool:
    """True when Pallas kernels lower natively (no interpret mode) here."""
    return jax.default_backend() in _PALLAS_BACKENDS


def resolve_use_pallas(use_pallas) -> bool:
    """Resolve an engine's ``use_pallas`` opt-in against the backend.

    ``False`` stays off.  ``True`` enables the kernels natively where
    Pallas lowers (a route that cannot lower there raises, never falls
    back); on CPU (interpret mode — slower than the plain reduction) it
    falls back to the jnp path with a warning (once per backend;
    :func:`reset_pallas_warning` re-arms it).  ``"force"`` runs the
    interpret-mode kernels on CPU, for tests; a native backend rejects it.
    """
    if not use_pallas:
        return False
    if pallas_native():
        if use_pallas == "force":
            raise ValueError(
                "use_pallas='force' runs the Pallas kernels in interpret "
                "mode, for CPU tests; on the "
                f"{jax.default_backend()!r} backend pass use_pallas=True "
                "(native kernels) or False")
        return True
    if use_pallas == "force":
        return True
    backend = jax.default_backend()
    if backend not in _warned_pallas_fallback:
        _warned_pallas_fallback.add(backend)
        warnings.warn(
            "use_pallas=True requested on the "
            f"{jax.default_backend()!r} backend, where the Pallas "
            "next-event kernel only runs in interpret mode (slower than "
            "the plain XLA reduction) — falling back to the jnp path. "
            "Pass use_pallas='force' to run the interpret-mode kernel "
            "anyway.", RuntimeWarning, stacklevel=3)
    return False


def attention_op(q: jax.Array, k: jax.Array, v: jax.Array, *,
                 causal: bool = True, interpret: bool = False) -> jax.Array:
    """Model layout adapter: q [B,S,H,hd], k/v [B,S,K,hd] → [B,S,H,hd]."""
    from .flash_attention import flash_attention
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = flash_attention(qt, kt, vt, causal=causal, interpret=interpret)
    return out.transpose(0, 2, 1, 3)


def next_event_op(times: jax.Array, mask: jax.Array | None = None, *,
                  interpret: bool | None = None):
    """Engine-layer adapter: fused masked (min, argmin) over the last axis.

    Used by the vectorized simulation engines (``vec_scheduler``,
    ``vec_cluster``, ``vec_workflow``) for the SoA next-event reduction.
    ``interpret=None`` resolves automatically: native lowering on TPU/GPU,
    interpret mode elsewhere (reached only via ``use_pallas="force"``).
    """
    from .next_event import next_event
    if interpret is None:
        interpret = not pallas_native()
    return next_event(times, mask, interpret=interpret)


# -- masked next-event-style reductions (the vec engines' shared ops) ----------
#
# Every vectorized engine reduces "which candidate happens next" to a masked
# min/argmin/argmax over an SoA candidate array.  These are the one canonical
# implementation (previously three private copies in vec_cluster / vec_power /
# vec_workflow), with the fused Pallas kernel behind the single ``use_pallas``
# switch.  Contracts (asserted by tests/test_masked_ops.py):
#
#   * reduction is over the **last** axis; ``mask=False`` slots are ignored;
#   * an all-masked (or empty-of-finite) input returns ``(inf, 0)`` exactly
#     like ``jnp.min``/``jnp.argmin`` over an all-inf array;
#   * ties break to the **first occurrence**, identically on the jnp and
#     Pallas paths (selection decisions are part of the engines' bit-
#     exactness contract);
#   * the jnp and Pallas paths agree bit-for-bit (min is exact).


def _masked(values, mask, fill):
    values = jnp.asarray(values)
    if mask is None:
        return values
    return jnp.where(mask, values, jnp.asarray(fill, values.dtype))


def masked_min(values, mask=None, *, use_pallas: bool = False):
    """Masked min over the last axis (``inf`` when everything is masked)."""
    if use_pallas:
        return next_event_op(values, mask)[0]
    return jnp.min(_masked(values, mask, jnp.inf), axis=-1)


def masked_argmin(values, mask=None, *, use_pallas: bool = False):
    """First-occurrence masked argmin over the last axis (0 when all masked)."""
    if use_pallas:
        return next_event_op(values, mask)[1]
    return jnp.argmin(_masked(values, mask, jnp.inf), axis=-1)


def masked_argmax(values, mask=None, *, use_pallas: bool = False):
    """First-occurrence masked argmax over the last axis (0 when all masked).

    The Pallas path reduces ``-values`` through the next-event kernel; the
    first occurrence of the minimum of ``-v`` is the first occurrence of the
    maximum of ``v``, so both paths share ``jnp.argmax``'s tie rule.
    """
    if use_pallas:
        return next_event_op(-values, mask)[1]
    return jnp.argmax(_masked(values, mask, -jnp.inf), axis=-1)


@dataclass(frozen=True)
class MaskedOps:
    """The masked-reduction ops bound to one resolved ``use_pallas`` switch.

    The :mod:`repro.core.vec_engine` driver hands an instance to every
    engine's ``build`` so scenario definitions write ``ops.min(...)`` /
    ``ops.argmin(...)`` without re-plumbing the Pallas opt-in.
    """

    use_pallas: bool = False

    def min(self, values, mask=None):
        return masked_min(values, mask, use_pallas=self.use_pallas)

    def argmin(self, values, mask=None):
        return masked_argmin(values, mask, use_pallas=self.use_pallas)

    def argmax(self, values, mask=None):
        return masked_argmax(values, mask, use_pallas=self.use_pallas)


def wkv6_op(r: jax.Array, k: jax.Array, v: jax.Array, logw: jax.Array,
            u: jax.Array, *, interpret: bool = False):
    """Model layout adapter: r/k/v/logw [B,S,H,N], u [H,N] →
    (y [B,S,H,N], state [B,H,N,N])."""
    from .rwkv6_scan import wkv6
    tr = lambda t: t.transpose(0, 2, 1, 3)
    y, state = wkv6(tr(r), tr(k), tr(v), tr(logw), u, interpret=interpret)
    return y.transpose(0, 2, 1, 3), state
