"""Percentile and rate arithmetic, the byte function, metric expressions."""

import pytest

from benchmark.harness import gauges, stats, workbytes
from benchmark.harness.metric_eval import Evaluator


def test_percentile_nearest_rank():
    v = list(range(1, 101))
    assert stats.percentile(v, 50) == 50
    assert stats.percentile(v, 95) == 95
    assert stats.percentile(v, 100) == 100
    assert stats.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_window_with_a_stall_keeps_the_stall():
    # 9 operations of 10 ms, then one that stalls 5 s and is answered
    # after the 2 s window closed: the rate counts what completed inside
    # plus the share of the stalled one's time that lay inside (1 s of its
    # 5 s), over the WHOLE window; the tail holds the stall.
    t0 = 100.0
    ops = [{"key": "a", "sent": t0 + i * 0.1, "done": t0 + i * 0.1 + 0.010,
            "ok": True} for i in range(9)]
    ops.append({"key": "a", "sent": t0 + 1.0, "done": t0 + 6.0, "ok": True})
    ops.append({"key": "a", "sent": t0 + 1.0, "done": t0 + 1.5,
                "ok": False})
    w = stats.window_metrics(ops, t0, 2.0)
    assert w["ops_per_s"] == pytest.approx((9 + 1.0 / 5.0) / 2.0)
    assert w["n_latencies"] == 10                 # the failed one has none
    assert w["latency_p50_ms"] == pytest.approx(10.0)
    assert w["latency_p95_ms"] == pytest.approx(5000.0)
    assert w["latency_sum_s"] == pytest.approx(9 * 0.010 + 5.0)


def _cycle(t0, costs, until):
    """A closed loop over `costs` ({key: seconds}) in order, from t0."""
    ops, t = [], t0
    while t < until:
        for k, c in costs.items():
            if t >= until:
                break
            ops.append({"key": k, "sent": t, "done": t + c, "ok": True})
            t += c
    return ops


def test_the_cycle_weighted_rate_does_not_depend_on_where_the_window_ends():
    # a round of four fast statements and one slow: 4 x 0.01 + 2.0 s.
    # Plain counts jump by 4 when the window's end passes the burst; the
    # weighted rate is 5 statements per 2.04 s wherever it ends.
    costs = {"a": 0.01, "b": 0.01, "c": 0.01, "d": 0.01, "slow": 2.0}
    want = 5 / 2.04
    plain, weighted = [], []
    for window in (10.0, 10.15, 10.25, 11.0, 12.2):
        ops = _cycle(0.0, costs, window)
        w = stats.window_metrics(ops, 0.0, window, stats.cycle_weights(ops))
        plain.append(w["ops_per_s_plain"])
        weighted.append(w["ops_per_s"])
    assert max(plain) / min(plain) > 1.15
    assert all(x == pytest.approx(want, rel=0.01) for x in weighted)
    # the median statement of the round is `c` (0.01 s) wherever the
    # window ends; and a plain percentile is what no weights give
    assert w["latency_p50_ms"] == pytest.approx(10.0)
    assert stats.weighted_percentile([3, 1, 2], [1, 1, 1], 50) == \
        stats.percentile([3, 1, 2], 50) == 2
    assert stats.weighted_percentile([1, 2, 3], [1, 1, 10], 50) == 3
    # equal costs: the weighted count is the plain count
    ops = _cycle(0.0, {"a": 0.5, "b": 0.5}, 10.0)
    w = stats.window_metrics(ops, 0.0, 10.0, stats.cycle_weights(ops))
    assert w["ops_per_s"] == pytest.approx(w["ops_per_s_plain"]) == 2.0


def test_hist_quantile_interpolates_inside_the_bucket():
    bounds = [1.0, 2.0, 4.0]
    assert stats.hist_quantile(bounds, [0, 10, 0, 0], 0.5) == \
        pytest.approx(1.5)
    assert stats.hist_quantile(bounds, [0, 0, 0, 5], 0.5) == 4.0   # +Inf
    assert stats.hist_quantile(bounds, [0, 0, 0, 0], 0.5) is None


def test_scan_bytes_counts_narrowest_widths():
    assert [workbytes.column_width(0, x) for x in
            (0, 255, 256, 65535, 65536, (1 << 32) - 1, 1 << 32)] == \
        [1, 1, 2, 2, 4, 4, 8]
    spec = {"where": [["AdvEngineID", "<>", 0]], "group_by": ["RegionID"],
            "select": [["key", "RegionID"], ["count", "*"],
                       ["avg", "ResolutionWidth"]]}
    assert workbytes.referenced_columns(spec) == \
        ["AdvEngineID", "RegionID", "ResolutionWidth"]
    widths = {"AdvEngineID": 1, "RegionID": 2, "ResolutionWidth": 2,
              "UserID": 8}
    assert workbytes.scan_bytes(spec, 1000, widths) == 5000


PROM = """# HELP serenedb_search_batch_queries x
# TYPE serenedb_search_batch_queries gauge
serenedb_search_batch_queries %d
serenedb_search_batch_dispatches %d
serenedb_query_latency_seconds_bucket{le="0.001"} %d
serenedb_query_latency_seconds_bucket{le="0.002"} %d
serenedb_query_latency_seconds_bucket{le="+Inf"} %d
serenedb_query_latency_seconds_sum %g
serenedb_query_latency_seconds_count %d
serenedb_statement_calls{queryid="1",query="x"} 5
"""


def _delta():
    a = gauges.parse_metrics(PROM % (10, 5, 1, 1, 1, 0.001, 1))
    b = gauges.parse_metrics(PROM % (50, 15, 1, 11, 11, 0.016, 11))
    for s in (a, b):
        s["programs"], s["dispatches"] = {}, 0
    return gauges.delta(a, b)


def test_gauge_expression_over_window_deltas():
    specs = {
        "batch": {"expr": "SearchBatchQueries / SearchBatchDispatches"},
        "p50": {"expr": '1000 * hist_q("QueryLatency", 0.5)'},
        "outside": {"expr": '100 * (1 - hist_sum("QueryLatency") / '
                            'client_latency_sum_s)'},
        "twice": {"expr": '2 * metric("batch")'},
        "absent_gauge": {"expr": "NoSuchGauge / 2"},
        "absent_value": {"expr": "trace_busy_s / trace_window_s"},
        "by_zero": {"expr": "SearchBatchQueries / (ops - ops)"},
        "unsafe": {"expr": "__import__('os').getpid()"},
    }
    ev = Evaluator(_delta(), {"client_latency_sum_s": 0.03, "ops": 3.0},
                   specs.__getitem__)
    assert ev.metric("batch") == pytest.approx(4.0)
    assert ev.metric("p50") == pytest.approx(1.5)     # 10 obs in (1, 2] ms
    assert ev.metric("outside") == pytest.approx(50.0)
    assert ev.metric("twice") == pytest.approx(8.0)
    # nothing to read -> nothing reported (never 0)
    assert ev.metric("absent_gauge") is None
    assert ev.metric("absent_value") is None
    assert ev.metric("by_zero") is None
    with pytest.raises(ValueError):
        ev.metric("unsafe")


def test_prom_name_follows_the_programs_rule():
    assert gauges.prom_name("SearchBatchQueries") == \
        "serenedb_search_batch_queries"
    assert gauges.prom_name("WalFsync") == "serenedb_wal_fsync"
