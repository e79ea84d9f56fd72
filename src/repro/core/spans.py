"""Host spans of the sweep path, written into the profiler's own trace.

``span(name)`` is a ``jax.profiler.TraceAnnotation``: with no profiler
trace running it records nothing and costs about half a microsecond; under
``jax.profiler.trace(dir)`` it lands on the host plane, on the same clock
as the device operations.  The spans nest on the calling thread, each
inside the ``sweep`` span of its ``run_sweep`` call:

======================  ===================================================
``sweep``               one ``run_sweep`` call; stat ``id`` (per process),
                        and at its end, while tracing, the sweep's
                        :class:`~repro.core.sweep.SweepReport` counters
                        (``report_fields()``, ``dispatches``)
``sweep.validate``      ``validate_scenario_params``
``sweep.prepare``       the engine's host build of the batch (``prepare``)
``sweep.prepare.build`` llmserve: one routing table per distinct request
                        stream, stat ``workloads`` (the streams drawn);
                        power: the demand traces, the fleet and its power
                        tables
``sweep.prepare.pack``  llmserve: each cell's columns gathered into the
                        lane params; power: the per-lane copies of the
                        params
``sweep.stage``         gathering lane inputs on the host (chunk gather,
                        state prototype, resident batch, refill rows)
``sweep.dispatch``      one executable call: argument staging, copies of
                        host arrays to the device, launch
``sweep.wait``          the host blocked on the device and on copies back
``sweep.retire``        compacting bookkeeping: scatter into the results,
                        the refill queue
``sweep.finalize``      the plan's host finalizer
======================  ===================================================

There is no span per cell or per lane: a sweep emits a few per dispatch.
"""
from __future__ import annotations

import itertools
from typing import Any, Dict

_sweep_ids = itertools.count(1)


def span(name: str, **stats: Any):
    """A host span named ``name`` (a context manager), with ``stats``."""
    from jax.profiler import TraceAnnotation   # jax stays a lazy import
    return TraceAnnotation(name, **stats)


def sweep_span():
    """The ``sweep`` span of one ``run_sweep`` call, with a fresh id."""
    return span("sweep", id=next(_sweep_ids))


def tracing() -> bool:
    """Whether a profiler trace is recording (spans then carry stats)."""
    from jax.profiler import TraceAnnotation
    return TraceAnnotation.is_enabled()


def report_stats(report) -> Dict[str, Any]:
    """A sweep report's counters as span stats: ``report_fields()`` and the
    number of executable calls, leaving out what is unset."""
    stats = dict(report.report_fields(), dispatches=report.dispatches)
    return {k: v for k, v in stats.items() if v is not None}
