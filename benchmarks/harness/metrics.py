"""End-to-end metric arithmetic over the generator's statement log.

Every metric is taken over the WHOLE window: all statements that completed
inside it and all of its seconds. No median of chunks, no trimming: a stall
inside the window moves the numbers, as it moves what a user sees.
"""

from __future__ import annotations

import math


def in_window(records: list[dict], w0: float, w1: float) -> list[dict]:
    """Statements that completed inside [w0, w1] without an error."""
    return [r for r in records
            if w0 <= r["t1"] <= w1 and "error" not in r]


def latency_s(rec: dict) -> float:
    """Closed loop: from send to last byte. Open loop: from when the
    statement was due, so a stall's wait is counted."""
    return rec["t1"] - rec.get("due", rec["t0"])


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile (sysbench's definition), q in (0, 100]."""
    if not values:
        raise ValueError("percentile of no samples")
    s = sorted(values)
    return s[max(0, math.ceil(q / 100.0 * len(s)) - 1)]


def by_class(records: list[dict]) -> dict[str, list[float]]:
    out: dict[str, list[float]] = {}
    for r in records:
        out.setdefault(r["class"], []).append(latency_s(r))
    return out


def end_to_end(records: list[dict], w0: float, w1: float,
               classes: dict[str, dict]) -> dict[str, float]:
    """{metric: value} for whichever end-to-end metrics the window's
    statements support. `classes` maps a class name to its statement file
    (`kind`, `rows_scanned`)."""
    done = in_window(records, w0, w1)
    seconds = w1 - w0
    lat = by_class(done)
    out: dict[str, float] = {}
    analytic = {c: v for c, v in lat.items()
                if classes[c]["kind"] == "analytic"}
    if analytic:
        if len(analytic) == sum(1 for c in classes.values()
                                if c["kind"] == "analytic"):
            means = [sum(v) / len(v) for v in analytic.values()]
            out["analytic_geomean_ms"] = 1e3 * math.exp(
                sum(math.log(m) for m in means) / len(means))
        out["analytic_rows_per_s"] = sum(
            classes[c]["rows_scanned"] * len(v)
            for c, v in analytic.items()) / seconds
    point = [x for c, v in lat.items()
             if classes[c]["kind"] == "point" for x in v]
    if point:
        out["point_p95_ms"] = 1e3 * percentile(point, 95)
    writes = sum(len(v) for c, v in lat.items()
                 if classes[c]["kind"] == "write")
    if writes:
        out["write_txn_per_s"] = writes / seconds
    return out
