"""Window arithmetic: rates and percentiles over ALL operations of the
window (a stall is inside the numbers, never trimmed)."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (q in (0, 100]) of a non-empty sequence:
    the smallest value with at least q% of the samples at or below it."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of no samples")
    rank = max(1, math.ceil(q / 100.0 * len(v)))
    return v[rank - 1]


def weighted_percentile(values, weights, q: float) -> float:
    """Nearest-rank percentile with weights: the smallest value at or
    below which at least q% of the total weight lies."""
    pairs = sorted(zip(values, weights))
    if not pairs:
        raise ValueError("percentile of no samples")
    target = q / 100.0 * sum(w for _, w in pairs)
    cum = 0.0
    for v, w in pairs:
        cum += w
        if cum >= target - 1e-12:
            return v
    return pairs[-1][0]


def cycle_weights(ops) -> dict:
    """{key: weight} for traffic that sends a fixed cycle of operations in
    order (`pick: file_order`): each operation weighs its key's share of a
    cycle's time, scaled so that one whole cycle of K keys weighs K. Then
    the weighted count over a window is K x (cycles done), wherever in the
    cycle the window closes: an estimate of the session's long-run rate
    (K operations per mean cycle time) that does not jump when a burst of
    fast statements falls just inside or just outside the window. The
    shares come from the window's own latencies (the median per key), so
    nothing is assumed about any statement. With equal costs every weight
    is 1 and the count is the plain count.

    The percentiles of such a cell weigh each operation 1 / (operations
    of its key in the window), for the same reason: in the long run every
    statement of the round is equally frequent, and a window that holds
    one more of the round's first statements than of its last would
    shift the rank, so that the median hops between instances."""
    lat: dict = {}
    for o in ops:
        if o["ok"]:
            lat.setdefault(o["key"], []).append(o["done"] - o["sent"])
    med = {k: percentile(v, 50) for k, v in lat.items()}
    total = sum(med.values())
    if total <= 0:
        return {k: 1.0 for k in med}
    return {k: len(med) * m / total for k, m in med.items()}


def window_metrics(ops, t0: float, seconds: float,
                   weights: dict = None) -> dict:
    """End-to-end numbers of one window.

    `ops`: dicts with `key`, `sent`, `done` (host monotonic seconds) and
    `ok` (answered, not errored), every operation SENT inside [t0,
    t0+seconds). The rate is the work done INSIDE the window over the
    whole window: an operation answered inside it counts its weight (1
    without `weights`, see `cycle_weights`), one still in flight when the
    window closed counts by the share of its time that lay inside (the
    harness waits for its answer, so the share is known; it has to be
    right to count at all). Latencies are of every operation sent in the
    window, also those answered after it closed (the wait counts)."""
    end = t0 + seconds
    done = [o for o in ops if o["ok"]]
    share = [1.0 if o["done"] <= end else
             (end - o["sent"]) / (o["done"] - o["sent"]) for o in done]
    lat = [(o["done"] - o["sent"]) * 1e3 for o in done]
    per_key: dict = {}
    for o in done:
        per_key[o["key"]] = per_key.get(o["key"], 0) + 1
    even = [1.0 / per_key[o["key"]] if weights else 1.0 for o in done]
    out = {"ops_per_s": sum(
               s * (weights[o["key"]] if weights else 1.0)
               for o, s in zip(done, share)) / seconds,
           "ops_per_s_plain": sum(1 for o in done if o["done"] <= end)
           / seconds,
           "n_latencies": len(lat)}
    if lat:
        out["latency_p50_ms"] = weighted_percentile(lat, even, 50)
        out["latency_p95_ms"] = weighted_percentile(lat, even, 95)
        out["latency_p50_ms_plain"] = percentile(lat, 50)
        out["latency_sum_s"] = sum(lat) / 1e3
    return out


def by_key(ops, most: int = 24) -> dict:
    """{key: [answered, median ms, slowest ms]} of a window's operations
    (the `most` keys that took most time in all): which statement a run's
    time went to, for whoever reads two runs that differ."""
    lat: dict = {}
    for o in ops:
        if o["ok"]:
            lat.setdefault(o["key"], []).append((o["done"] - o["sent"]) * 1e3)
    top = sorted(lat, key=lambda k: -sum(lat[k]))[:most]
    return {k: [len(lat[k]), percentile(lat[k], 50), max(lat[k])]
            for k in sorted(top)}


def hist_quantile(bounds, counts, q: float):
    """Quantile of a bucketed histogram (counts per bucket, NOT
    cumulative; bounds[i] = upper edge of bucket i, the last count is the
    +Inf bucket) by linear interpolation inside the bucket — what
    Prometheus' histogram_quantile() gives. None when empty."""
    total = sum(counts)
    if total <= 0:
        return None
    target = q * total
    cum = 0.0
    for i, c in enumerate(counts):
        if c <= 0:
            continue
        if cum + c >= target:
            if i >= len(bounds):
                return float(bounds[-1])
            lo = float(bounds[i - 1]) if i else 0.0
            hi = float(bounds[i])
            return lo + (hi - lo) * ((target - cum) / c)
        cum += c
    return float(bounds[-1])
