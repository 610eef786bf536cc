"""The comparison that decides ``correct``.

Each sampled answer is one input's final activation column. A
configuration's ``limits`` name the numbers compared for it, each with
its limit; PERF.md gives the readings each limit was set from and why
the other numbers here are read but not compared.

* ``gap_p75`` -- over the sampled inputs the reference keeps alive, the
  75th percentile of each column's relative gap ``|y - ref| / |ref|``
  (2-norms, float64). Compared. A quarter of the answers may be wrong
  before it moves, so one input whose early layers sit at the ReLU
  threshold, which float32 sums in another order can tip, does not
  decide it; a step that gets a quarter of its answers wrong does.
* ``gap_p50`` -- the median of the same.
* ``value_gap`` -- the widest gap of any final activation, as a share of
  its column's largest reference activation (at least 1). It swings with
  that one input at the threshold.
* ``category_mismatch`` -- sampled inputs whose challenge category (any
  positive final activation, as the program reported it) differs from
  the reference's.
* ``lost_answers`` -- answers due in the window that never came
  (failed steps, quarantined columns). Exact: compared with limit 0.
"""

from __future__ import annotations

import numpy as np

UNREADABLE = 1e300  # stands in for a gap that is not a finite number


def _finite(gap: float) -> float:
    return gap if np.isfinite(gap) else UNREADABLE


def value_gap(y: np.ndarray, ref: np.ndarray) -> float:
    if not np.size(ref):
        return 0.0
    y = np.asarray(y, np.float64)
    ref = np.asarray(ref, np.float64)
    scale = np.maximum(np.abs(ref).max(axis=0), 1.0)
    return _finite(float((np.abs(y - ref) / scale).max()))


def column_gaps(y: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Relative gap of each column the reference keeps alive."""
    if not np.size(ref):
        return np.zeros(0)
    live = np.asarray(ref).max(axis=0) > 0
    y = np.asarray(y, np.float64)[:, live]
    ref = np.asarray(ref, np.float64)[:, live]
    return np.linalg.norm(y - ref, axis=0) / np.linalg.norm(ref, axis=0)


def numbers(y: np.ndarray, mask: np.ndarray, ref: np.ndarray, *, lost: int) -> dict:
    """Every number the comparison reads, for the sampled columns ``y``
    (their masks as the program reported them) against ``ref``."""
    gaps = column_gaps(y, ref)
    q = lambda p: _finite(float(np.percentile(gaps, p))) if len(gaps) else 0.0  # noqa: E731
    return {
        "gap_p75": q(75),
        "gap_p50": q(50),
        "value_gap": value_gap(y, ref),
        "category_mismatch": int((np.asarray(mask, bool) != (ref.max(axis=0) > 0)).sum()),
        "lost_answers": int(lost),
    }


def compare_columns(
    y: np.ndarray, mask: np.ndarray, ref: np.ndarray, limits: dict, *, lost: int
) -> dict:
    """``{name: {"value", "limit"}}`` of the numbers ``limits`` names."""
    values = numbers(y, mask, ref, lost=lost)
    return {k: {"value": values[k], "limit": v} for k, v in limits.items()}
