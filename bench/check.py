"""The comparison that decides ``correct``: outputs of the timed path
against the plain reference, cell by cell.

The number compared is ``max_rel_gap``: over every output and every
sampled cell, the largest gap between the program's values and the
reference's, divided by the largest magnitude of that output in that cell
(at least 1 for integer and bool outputs).  Infinities must match in place
and sign, and a missing output, a shape that differs or a NaN reads as an
infinite gap.  An integer that differs by one therefore reads at least
``1 / max(|reference|)``, far above any float limit.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

INF = float("inf")


def output_gap(prog: np.ndarray, ref: np.ndarray) -> float:
    """Worst per-cell normwise relative gap of one output ``[C, ...]``."""
    a, b = np.asarray(prog), np.asarray(ref)
    if a.shape != b.shape:
        return INF
    if a.size == 0:
        return 0.0
    a = a.reshape(len(a), -1).astype(np.float64)
    b = b.reshape(len(b), -1).astype(np.float64)
    if np.isnan(a).any() or np.isnan(b).any():
        return INF
    fin_b = np.isfinite(b)
    if np.any(np.isfinite(a) != fin_b) or np.any(a[~fin_b] != b[~fin_b]):
        return INF
    floor = 1.0 if np.asarray(ref).dtype.kind in "biu" else \
        np.finfo(np.float64).tiny
    scale = np.maximum(np.max(np.where(fin_b, np.abs(b), 0.0), axis=1), floor)
    diff = np.where(fin_b, np.abs(a - np.where(fin_b, b, 0.0)), 0.0)
    return float(np.max(np.max(diff, axis=1) / scale))


def compare(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray]
            ) -> Tuple[float, Dict[str, float]]:
    """``(max_rel_gap, {output: gap})`` over every reference output."""
    gaps = {k: (output_gap(prog[k], ref[k]) if k in prog else INF)
            for k in sorted(ref)}
    return max(gaps.values(), default=0.0), gaps
