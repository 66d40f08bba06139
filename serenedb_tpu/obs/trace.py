"""Lock-cheap per-query span/profile collector.

Reference analog: ClickHouse's per-query ProfileEvents and PG's
EXPLAIN ANALYZE instrumentation, re-expressed for the morsel/batch
executor: every PlanNode's batch generator is wrapped (exec/plan.py
auto-wraps subclasses), and the fused morsel pipeline stamps its stage
work directly (exec/morsel.py), so both the streaming operator tree and
the worker-pool path are covered by ONE collector.

Determinism contract: profiling observes, never steers. Each executing
thread accumulates into its own bucket (a thread-local dict — no lock on
the hot path after first touch); the sink merges buckets by summing
integer counters, so the merged numbers are independent of scheduling
order and the query result is bit-identical with profiling on or off at
any `serene_workers`. Wall nanoseconds in morsel pipelines are
summed per-worker task times (they can exceed elapsed wall clock on
purpose — that is the work the pool did).
"""

from __future__ import annotations

import contextvars
import itertools
import sys
import threading
import time
from collections import OrderedDict
from typing import Iterator, Optional

from ..utils import metrics

#: additive per-operator counters (merge = sum; scheduling-order free)
_COUNTERS = ("wall_ns", "rows_out", "loops", "morsels_scheduled", "morsels_pruned",
             "morsels_jf_pruned", "device_ns", "batch_queries",
             "batch_window_ns", "batch_scoring_ns", "shard_pipelines",
             "shard_pruned", "shard_collective",
             "device_prog_hits", "device_prog_misses")


class OpStats:
    """One operator's accumulated span counters (one bucket's view)."""

    __slots__ = _COUNTERS + ("first_ns", "device_declined")

    def __init__(self):
        for f in _COUNTERS:
            setattr(self, f, 0)
        #: the operator's accumulated wall ns at its FIRST emitted batch
        #: (PG "startup time"; merge = min, thread-order free)
        self.first_ns: Optional[int] = None
        #: fused-tier decline reason slug (non-additive: one execution
        #: declines for one reason; merge keeps any observed value)
        self.device_declined: Optional[str] = None

    def merge(self, other: "OpStats") -> None:
        for f in _COUNTERS:
            setattr(self, f, getattr(self, f) + getattr(other, f))
        if other.first_ns is not None:
            self.first_ns = other.first_ns if self.first_ns is None \
                else min(self.first_ns, other.first_ns)
        if other.device_declined is not None:
            self.device_declined = other.device_declined


def batch_nbytes(b) -> int:
    """Materialized bytes of a batch's physical arrays (dictionary pages
    are shared, not per-batch — excluded)."""
    return sum(int(c.data.nbytes) for c in b.columns)


class QueryProfile:
    """Per-query collector keyed by id(plan node).

    Hot-path cost is one thread-local dict lookup plus integer adds per
    BATCH (never per row); batches are morsel-sized.
    """

    def __init__(self):
        self._register_lock = threading.Lock()
        self._buckets: list[dict[int, OpStats]] = []
        self._tl = threading.local()
        self.t0_ns = time.perf_counter_ns()

    # -- accumulation (any thread) ----------------------------------------

    def _bucket(self) -> dict[int, OpStats]:
        d = getattr(self._tl, "d", None)
        if d is None:
            d = self._tl.d = {}
            with self._register_lock:
                self._buckets.append(d)
        return d

    def stats(self, key: int) -> OpStats:
        d = self._bucket()
        s = d.get(key)
        if s is None:
            s = d[key] = OpStats()
        return s

    def add_scan_morsels(self, key: int, scheduled: int = 0,
                         pruned: int = 0, jf_pruned: int = 0) -> None:
        """Morsel scheduling outcome for one scan. The three counters are
        DISJOINT (scheduled + pruned + jf_pruned = blocks considered):
        `pruned` is zone-map-only pruning, `jf_pruned` join-filter
        pruning, a block both would skip counts once under the join
        filter — so roll-ups never double-count a block."""
        s = self.stats(key)
        s.morsels_scheduled += int(scheduled)
        s.morsels_pruned += int(pruned)
        s.morsels_jf_pruned += int(jf_pruned)

    def add_stage(self, key: int, rows_out: int, wall_ns: int) -> None:
        """Fused-pipeline stamp: one morsel's pass through one operator
        (the operator's own batches() never runs in the fused path)."""
        s = self.stats(key)
        s.rows_out += int(rows_out)
        s.wall_ns += int(wall_ns)

    def add_device_ns(self, key: int, ns: int) -> None:
        self.stats(key).device_ns += int(ns)

    def add_search_batch(self, key: int, queries: int, window_ns: int,
                         scoring_ns: int) -> None:
        """Search-batcher span for one top-k scan: how many queries its
        dispatch carried (1 = no coalescing), how long this query waited
        queued, and the shared scoring time of the whole dispatch — so
        EXPLAIN ANALYZE attributes both the batching win and its latency
        cost."""
        s = self.stats(key)
        s.batch_queries += int(queries)
        s.batch_window_ns += int(window_ns)
        s.batch_scoring_ns += int(scoring_ns)

    def add_shards(self, key: int, pipelines: int, pruned: int = 0,
                   collective: int = 0) -> None:
        """Sharded-tier span for one operator: how many per-shard
        pipelines its execution fanned out into (serene_shards > 1),
        how many blocks the shard-to-shard join filter pruned, and how
        many of the pipelines were combined IN-PROGRAM by a collective
        shard_map dispatch (serene_shard_combine=device) — the
        `Shards:` EXPLAIN ANALYZE detail line's n=/pruned=/combine=.
        All three are additive ints, so the order-free sink merge
        applies unchanged."""
        s = self.stats(key)
        s.shard_pipelines += int(pipelines)
        s.shard_pruned += int(pruned)
        s.shard_collective += int(collective)

    def wrap_batches(self, node, fn, ctx) -> Iterator:
        """Instrumented drive of a node's raw batch generator: wall time
        accrues only while inside next() (inclusive of children, PG
        semantics), rows per emitted batch."""
        key = id(node)
        self.stats(key).loops += 1
        it = fn(node, ctx)
        try:
            while True:
                t0 = time.perf_counter_ns()
                try:
                    b = next(it)
                except StopIteration:
                    self.stats(key).wall_ns += time.perf_counter_ns() - t0
                    return
                t1 = time.perf_counter_ns()
                s = self.stats(key)
                s.wall_ns += t1 - t0
                if s.first_ns is None:
                    s.first_ns = s.wall_ns
                s.rows_out += b.num_rows
                yield b
        finally:
            it.close()

    # -- sink merge (call after execution has drained) --------------------

    def merged(self) -> dict[int, OpStats]:
        """Deterministic sink merge: per-thread buckets sum into one map.
        Integer addition is order-free, so the result is identical for
        any scheduling of the same work."""
        with self._register_lock:
            buckets = list(self._buckets)
        out: dict[int, OpStats] = {}
        for d in buckets:
            for key, s in d.items():
                agg = out.get(key)
                if agg is None:
                    out[key] = agg = OpStats()
                agg.merge(s)
        return out

    def totals(self) -> OpStats:
        """Whole-query roll-up of the prune counters (stat_statements
        attribution); rows/time roll-ups are per-operator, not summed."""
        t = OpStats()
        for s in self.merged().values():
            t.morsels_scheduled += s.morsels_scheduled
            t.morsels_pruned += s.morsels_pruned
            t.morsels_jf_pruned += s.morsels_jf_pruned
            t.device_ns += s.device_ns
        return t


# -- timeline tracing (serene_trace) ------------------------------------------
#
# The QueryProfile above answers "how much" per operator; the timeline
# layer answers "WHEN": every request gets a trace id and timestamped
# span events — (id, parent, name, category, begin ns, end ns, detail)
# — recorded into per-thread rings (a plain-list append under the GIL,
# no lock on the hot path after first touch, the same bucket pattern
# QueryProfile uses), so the front door's hops, the pool's queue waits,
# batcher coalescing windows, shard fan-outs and the device phases
# become one Chrome-trace-loadable timeline from the socket to the last
# flushed row. The trace and the current span propagate across the
# worker pool via the CURRENT_TRACE / CURRENT_SPAN contextvars (pool
# tasks copy the submitter's context), and a coalesced search dispatch
# stamps its spans under EVERY member query's trace.
#
# A small fixed vocabulary of spans are STAGES (`STAGES` below): they
# cut the request's timeline into pieces that do not overlap
# (`partition_stages`), each is also a
# `jax.profiler.TraceAnnotation("sdb.<stage>")` while it is open (so the
# profiler's host plane carries the same names on the device's clock),
# and `finish()` sums them into one histogram each plus `StageOther`, so
# that per request  sum(stages) + other == request  to the nanosecond. Envelopes (the root, `execute`) and detail spans
# (`task`, `queue_wait`, `morsel_pipeline`, ...) are never annotated and
# never summed.
#
# Like the profiler, tracing observes only — results are bit-identical
# with it on or off at any worker/shard count.

#: per-thread span ring cap: a runaway span producer degrades to
#: counting drops instead of growing without bound
TRACE_RING_CAP = 8192

#: the stage vocabulary, fixed like the fused-tier decline reasons: one
#: `Stage*` histogram each (utils/metrics.py), one observation per
#: request = the stage's summed time in that request
STAGES = ("fd_parse", "fd_queue", "fd_encode", "cache_probe", "plan",
          "device_prepare", "device_upload", "device_enqueue",
          "device_wait",
          "device_finalize", "host_scan", "host_concat", "host_group",
          "host_sort", "host_join", "batch_wait", "search_plan", "search_phrase",
          "search_host_score")

_TRACE_IDS = itertools.count(1)

#: the executing statement's QueryTrace (None outside a traced
#: statement). Pool tasks capture the submitter's context at submit
#: time, so worker-thread spans land in the right query's timeline.
CURRENT_TRACE: contextvars.ContextVar = contextvars.ContextVar(
    "sdb_current_trace", default=None)

#: the innermost open span of this context: (trace, span id) or None.
#: It rides the same context copy as CURRENT_TRACE, so
#: a pool task's spans name the span that submitted them as parent.
CURRENT_SPAN: contextvars.ContextVar = contextvars.ContextVar(
    "sdb_current_span", default=None)


#: where the stages of a piece of work done on behalf of OTHER requests
#: are also noted as (name, begin ns, end ns): the search batcher's
#: coalesced dispatch runs on one member's thread and stamps its stages
#: under every member's trace afterwards. Rides the context copy like
#: the two above, so pool tasks of the dispatch note theirs too.
STAGE_SINK: contextvars.ContextVar = contextvars.ContextVar(
    "sdb_stage_sink", default=None)


def current_trace():
    """The executing statement's trace, or None (tracing off / outside
    a statement). One contextvar read — cheap enough for hot-ish paths."""
    return CURRENT_TRACE.get()


class _NoSpan:
    """What `stage()` / `span()` hand out when no trace is current."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NO_SPAN = _NoSpan()


def stage(name: str, **detail):
    """`with stage("plan"):` — one piece of the current request's
    timeline (a no-op outside a traced request, unless a `stage_sink`
    collects for others)."""
    tr = CURRENT_TRACE.get()
    if tr is None:
        sink = STAGE_SINK.get()
        return _NO_SPAN if sink is None else _SinkSpan(name, sink)
    return _Span(tr, name, "stage", detail)


class stage_sink:
    """`with stage_sink() as sink:` — every stage closed inside, on this
    thread or in pool tasks submitted from it, is also appended to
    `sink` as (name, begin ns, end ns), whether or not the running
    context has a trace of its own."""

    __slots__ = ("sink", "tok")

    def __enter__(self) -> list:
        self.sink: list = []
        self.tok = STAGE_SINK.set(self.sink)
        return self.sink

    def __exit__(self, *exc):
        STAGE_SINK.reset(self.tok)
        return False


class _SinkSpan:
    """A stage of a context with no trace, noted for a `stage_sink`."""

    __slots__ = ("name", "sink", "t0")

    def __init__(self, name: str, sink: list):
        self.name = name
        self.sink = sink

    def __enter__(self):
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.sink.append((self.name, self.t0, time.perf_counter_ns()))
        return False


def stage_of(tr: Optional["QueryTrace"], name: str):
    """`with stage_of(tr, "fd_encode"):` where the trace is held, not
    current (the front door's event loop), and may be None."""
    return _NO_SPAN if tr is None else _Span(tr, name, "stage", None)


def span(name: str, cat: str, **detail):
    """`with span("execute", "exec"):` — an envelope or detail span of
    the current request: parented and timed, never annotated or summed."""
    tr = CURRENT_TRACE.get()
    return _NO_SPAN if tr is None else _Span(tr, name, cat, detail)


_ANNOTATION = None


def _annotate(name: str, trace_id: int):
    """Enter `jax.profiler.TraceAnnotation("sdb.<name>")` — only in a
    process that has imported jax already (no backend is initialised
    for a span); one flag read in the profiler when no trace runs."""
    global _ANNOTATION
    if _ANNOTATION is None:
        jax = sys.modules.get("jax")
        if jax is None:
            return None
        _ANNOTATION = jax.profiler.TraceAnnotation
    ann = _ANNOTATION("sdb." + name, trace_id=trace_id)
    ann.__enter__()
    return ann


class _Span:
    """One open span. Entering makes it the context's current span (so
    spans opened inside — on this thread or in pool tasks submitted from
    it — name it as parent); leaving records it."""

    __slots__ = ("tr", "name", "cat", "detail", "sid", "parent", "t0",
                 "tok", "ann")

    def __init__(self, tr: "QueryTrace", name: str, cat: str, detail):
        self.tr = tr
        self.name = name
        self.cat = cat
        self.detail = detail
        self.ann = None

    def __enter__(self):
        tr = self.tr
        self.parent = tr.current_span()
        self.sid = next(tr._ids)
        self.tok = CURRENT_SPAN.set((tr, self.sid))
        if self.cat == "stage":
            self.ann = _annotate(self.name, tr.trace_id)
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        if self.ann is not None:
            self.ann.__exit__(None, None, None)
        CURRENT_SPAN.reset(self.tok)
        self.tr._push(self.sid, self.parent, self.name, self.cat,
                      self.t0, t1, self.detail or None)
        if self.cat == "stage":
            sink = STAGE_SINK.get()
            if sink is not None:
                sink.append((self.name, self.t0, t1))
        return False


class _Ring:
    __slots__ = ("tid", "thread_name", "spans", "dropped")

    def __init__(self, tid: int, thread_name: str):
        self.tid = tid
        self.thread_name = thread_name
        self.spans: list[tuple] = []
        self.dropped = 0


def partition_stages(stage_spans: list, dur: int) -> tuple[dict, list]:
    """One request of `dur` ns cut by its stage spans [(begin, end,
    name), ...] (offsets from the request's start): ({stage: ns} +
    "other", the timeline as [name, begin, end] pieces in order). Stages
    may nest (a stage opened inside `device_prepare`) and, where workers
    of one statement run side by side, overlap: at every instant the
    request is in the stage that BEGAN LAST among those open — the
    innermost on a thread — so each instant is counted once and
    sum(stages) + other == dur exactly, in integers."""
    sums: dict = {}
    edges = []
    for i, (b, e, name) in enumerate(stage_spans):
        sums.setdefault(name, 0)
        b, e = max(b, 0), min(e, dur)
        if e > b:
            edges.append((b, 1, i))
            edges.append((e, 0, i))
    edges.sort()                      # at one instant: ends before begins
    timeline: list = []
    open_: list = []
    prev = 0
    for t, opens, i in edges:
        if open_ and t > prev:
            name = stage_spans[max(
                open_, key=lambda j: (stage_spans[j][0], j))][2]
            sums[name] += t - prev
            if timeline and timeline[-1][0] == name and \
                    timeline[-1][2] == prev:
                timeline[-1][2] = t
            else:
                timeline.append([name, prev, t])
        prev = t
        if opens:
            open_.append(i)
        else:
            open_.remove(i)
    sums["other"] = dur - sum(sums.values())
    return sums, timeline


class QueryTrace:
    """One request's span-event collector.

    Spans are recorded at END time with explicit (begin, end)
    perf_counter_ns stamps, so within a thread they nest properly by
    construction (a span closes only after every span it started inside
    it). Every span has an id and names its parent (0 = the root).
    `stage()` / `span()` are the context-manager primitive; `add` stamps
    a span whose begin and end were read elsewhere (waits that belong to
    no thread). All append to the calling thread's ring; rings merge at
    `finish()` into one begin-ordered span list with ns offsets relative
    to the trace start."""

    __slots__ = ("trace_id", "query", "t0_ns", "t0_epoch_us", "end_ns",
                 "error", "peak_bytes", "cache_hit", "entry", "owned",
                 "_ids", "_register_lock", "_rings", "_tl", "_cv_token")

    def __init__(self, query_text: str = "", t0_ns: Optional[int] = None):
        self.trace_id = next(_TRACE_IDS)
        self.query = query_text
        now = time.perf_counter_ns()
        #: the request's start: the front door's receipt stamp when it
        #: began the trace, else now
        self.t0_ns = now if t0_ns is None else t0_ns
        self.t0_epoch_us = int(time.time() * 1e6) - (now - self.t0_ns) // 1000
        self.end_ns: Optional[int] = None
        self.error: Optional[str] = None
        #: stamped by the engine at statement end (serene_mem_account)
        self.peak_bytes: Optional[int] = None
        #: the engine served this statement from the result cache
        self.cache_hit = False
        #: the flight-recorder entry, once finished
        self.entry: Optional[dict] = None
        #: the engine began this trace itself (no front door) and ends
        #: the request where the statement ends
        self.owned = True
        self._ids = itertools.count(1)
        self._register_lock = threading.Lock()
        self._rings: list[_Ring] = []
        self._tl = threading.local()
        self._cv_token = None

    # -- recording (any thread) -------------------------------------------

    def _push(self, sid: int, parent: int, name: str, cat: str,
              begin_ns: int, end_ns: int, detail) -> None:
        r = getattr(self._tl, "r", None)
        if r is None:
            t = threading.current_thread()
            r = self._tl.r = _Ring(t.ident or 0, t.name)
            with self._register_lock:
                self._rings.append(r)
        if len(r.spans) >= TRACE_RING_CAP:
            r.dropped += 1
            return
        r.spans.append((sid, parent, name, cat, begin_ns,
                        max(end_ns, begin_ns), detail))

    def current_span(self, ctx: Optional[contextvars.Context] = None) -> int:
        """The id of the innermost open span of this trace in the
        calling context — or in a captured one (a pool task's, a batcher
        member's): the parent of what is stamped there, 0 = the root."""
        cur = CURRENT_SPAN.get() if ctx is None else ctx.get(CURRENT_SPAN)
        return cur[1] if cur is not None and cur[0] is self else 0

    def add(self, name: str, cat: str, begin_ns: int, end_ns: int,
            _parent: Optional[int] = None, _id: Optional[int] = None,
            **detail) -> None:
        """Record one span event whose stamps were read elsewhere, from
        any thread. begin/end are perf_counter_ns stamps (end >= begin
        enforced); detail keys become Chrome trace `args`; the parent is
        the context's current span unless given, the id a fresh one
        unless reserved (`new_span_id`). Span names are
        free-form; the "device" category carries device_compile,
        collective_dispatch and the posting pool's posting_upload
        (staged page writes) / posting_dispatch (batched
        gather-accumulate scoring) spans, "search" the batcher's
        batch_wait / batch_dispatch pair, "stage" a piece of the
        request's timeline (`add_stage`)."""
        self._push(next(self._ids) if _id is None else _id,
                   self.current_span() if _parent is None else _parent,
                   name, cat, begin_ns, end_ns, detail or None)

    def add_stage(self, name: str, begin_ns: int, end_ns: int) -> None:
        """A stage that belongs to no thread (`fd_queue`: submit ->
        callable starts) or whose stamps predate the trace (`fd_parse`):
        explicit begin/end, no profiler annotation."""
        self._push(next(self._ids), 0, name, "stage", begin_ns, end_ns,
                   None)

    def run_span(self, name: str, cat: str, fn, *args):
        """fn(*args) inside a span — what a pool worker runs in the
        task's captured context, so the span's parent is the span that
        submitted the task."""
        with _Span(self, name, cat, None):
            return fn(*args)

    def pinned(self, span_id: int = 0):
        """Make this trace (and `span_id` as the enclosing span) current
        for a block: same-thread set/reset pairs, for generators that
        resume on arbitrary threads."""
        return _Pin(self, span_id)

    def new_span_id(self) -> int:
        """An id for an envelope that is recorded when it ends but has
        to be named as parent while it is open (`execute`)."""
        return next(self._ids)

    # -- sink --------------------------------------------------------------

    def snapshot(self, end_ns: Optional[int] = None) -> dict:
        """The flight-recorder entry as of now (or `end_ns`): the root
        `query` span, the per-thread rings merged into one begin-ordered
        span list (offsets relative to the trace start) and the timeline
        cut into its stages (`partition_stages`). No side effects — the
        slow-query log reads a request that is still open through this."""
        if end_ns is None:
            end_ns = time.perf_counter_ns()
        dur = end_ns - self.t0_ns
        with self._register_lock:
            rings = list(self._rings)
        spans = [{"id": 0, "parent": None, "name": "query", "cat": "query",
                  "tid": 0, "thread": "query", "begin_ns": 0,
                  "end_ns": dur,
                  "args": {"query": self.query[:500],
                           "trace_id": self.trace_id}}]
        dropped = 0
        stage_spans = []
        for r in rings:
            dropped += r.dropped
            for sid, parent, name, cat, b, e, detail in list(r.spans):
                b -= self.t0_ns
                e -= self.t0_ns
                spans.append({"id": sid, "parent": parent, "name": name,
                              "cat": cat, "tid": r.tid,
                              "thread": r.thread_name,
                              "begin_ns": b, "end_ns": e,
                              "args": detail})
                if cat == "stage":
                    stage_spans.append((b, e, name))
        spans.sort(key=lambda s: (s["begin_ns"], -s["end_ns"]))
        stages, timeline = partition_stages(stage_spans, dur)
        # statement text truncates at entry-build time: every consumer
        # (listing, /_stats, chrome otherData) shows <= 500 chars, and
        # the always-on ring must not pin multi-MB INSERT literals
        return {"trace_id": self.trace_id, "query": self.query[:500],
                "begin_epoch_us": self.t0_epoch_us,
                "duration_ns": dur, "error": self.error,
                "spans": spans, "spans_dropped": dropped,
                # the request's timeline by stage: ns per stage (the
                # values add up to duration_ns) and the pieces in order
                "stages": stages, "timeline": timeline,
                # "device" | "host" | "cache", stamped by finish()
                "answered": None,
                # the engine's stamp when serene_mem_account ran: the
                # statement's accounted peak bytes
                "peak_bytes": self.peak_bytes}

    def finish(self, error: Optional[str] = None) -> dict:
        """Close the request: build the entry (`snapshot`), observe
        `RequestLatency`, one `Stage*` histogram per stage that occurred
        plus `StageOther`, and the answered-by counter (device: the
        timeline holds a `device_enqueue`; a result-cache hit counts as
        neither; errors are not answers). Idempotent."""
        if self.entry is not None:
            return self.entry
        self.end_ns = time.perf_counter_ns()
        if error is not None:
            self.error = error
        entry = self.snapshot(self.end_ns)
        if entry["spans_dropped"]:
            metrics.TRACE_SPANS_DROPPED.add(entry["spans_dropped"])
        metrics.REQUEST_LATENCY_HIST.observe_ns(entry["duration_ns"])
        for name, ns in entry["stages"].items():
            metrics.STAGE_HISTS[name].observe_ns(ns)
        if self.error is None:
            if "device_enqueue" in entry["stages"]:
                entry["answered"] = "device"
                metrics.STATEMENTS_ANSWERED_DEVICE.add()
            elif self.cache_hit:
                entry["answered"] = "cache"
            else:
                entry["answered"] = "host"
                metrics.STATEMENTS_ANSWERED_HOST.add()
        self.entry = entry
        return entry


class _Pin:
    __slots__ = ("tr", "sid", "t1", "t2")

    def __init__(self, tr: QueryTrace, sid: int):
        self.tr = tr
        self.sid = sid

    def __enter__(self):
        self.t1 = CURRENT_TRACE.set(self.tr)
        self.t2 = CURRENT_SPAN.set((self.tr, self.sid))
        return self.tr

    def __exit__(self, *exc):
        CURRENT_SPAN.reset(self.t2)
        CURRENT_TRACE.reset(self.t1)
        return False


def begin_request(label: str, enabled: bool,
                  t0_ns: Optional[int] = None) -> Optional[QueryTrace]:
    """The front door's half of a request: a trace that began when the
    message was received (`t0_ns`), handed to `execute_statement` /
    `execute_streaming`, which adopt it; `end_request` closes it once
    the response's last byte went to the transport. None when the
    session has `serene_trace` off."""
    if not enabled:
        return None
    tr = QueryTrace(label, t0_ns)
    tr.owned = False
    return tr


def end_request(tr: Optional[QueryTrace],
                error: Optional[str] = None) -> Optional[dict]:
    """Finish a request's trace into the flight recorder (once: a trace
    that is already closed is left alone). Success AND error paths — a
    failed statement's timeline is exactly the one worth keeping."""
    if tr is None:
        return None
    if tr.entry is not None:
        return tr.entry
    return FLIGHT.record(tr.finish(error))


class FlightRecorder:
    """Always-on bounded ring of the last N completed query timelines
    (`serene_flight_recorder_queries`, default 64): the slow-query log
    and error paths read a stall's timeline AFTER the fact instead of
    asking for a reproduction. One short lock per statement END."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: "OrderedDict[int, dict]" = OrderedDict()

    def _cap(self) -> int:
        from ..utils.config import REGISTRY
        try:
            return max(1, int(REGISTRY.get_global(
                "serene_flight_recorder_queries")))
        except KeyError:  # pragma: no cover — registry declares it
            return 64

    def record(self, entry: dict) -> dict:
        cap = self._cap()
        with self._lock:
            self._entries[entry["trace_id"]] = entry
            while len(self._entries) > cap:
                self._entries.popitem(last=False)   # oldest completes out
        metrics.TRACES_RECORDED.add()
        return entry

    def get(self, trace_id: int) -> Optional[dict]:
        with self._lock:
            return self._entries.get(int(trace_id))

    def last(self) -> Optional[dict]:
        with self._lock:
            if not self._entries:
                return None
            return next(reversed(self._entries.values()))

    def snapshot(self) -> list[dict]:
        """Newest-last entry list (shared references — treat as
        read-only)."""
        with self._lock:
            return list(self._entries.values())

    def reset(self) -> None:
        with self._lock:
            self._entries.clear()


#: process-wide flight recorder (one per process, like the metrics
#: registry)
FLIGHT = FlightRecorder()


def flight_summary(entry: dict) -> dict:
    """One flight entry as the compact listing dict — the single shape
    behind the GET /trace index and /_stats.traces, so the surfaces
    can't drift field by field."""
    return {"trace_id": entry["trace_id"],
            "query": entry["query"][:200],
            "duration_ms": round(entry["duration_ns"] / 1e6, 3),
            "spans": len(entry["spans"]),
            "spans_dropped": entry["spans_dropped"],
            "peak_bytes": entry.get("peak_bytes"),
            "error": entry["error"]}


def top_spans(entry: dict, n: int = 5) -> list[dict]:
    """The n widest non-root spans of a recorded timeline (slow-query
    log attachment)."""
    inner = [s for s in entry["spans"] if s["cat"] != "query"]
    inner.sort(key=lambda s: s["end_ns"] - s["begin_ns"], reverse=True)
    return inner[:n]


def format_top_spans(entry: dict, n: int = 5) -> list[str]:
    lines = [f"timeline: trace_id={entry['trace_id']} "
             f"duration={_ms(entry['duration_ns'])} ms "
             f"spans={len(entry['spans'])}"]
    for s in top_spans(entry, n):
        det = ""
        if s["args"]:
            det = " " + " ".join(f"{k}={v}" for k, v in s["args"].items())
        lines.append(
            f"  span {s['cat']}/{s['name']} "
            f"[{_ms(s['begin_ns'])}..{_ms(s['end_ns'])} ms] "
            f"thread={s['thread']}{det}")
    return lines


def chrome_trace(entry: dict) -> dict:
    """One flight-recorder entry as Chrome trace-event JSON (`ph: "X"`
    complete events, ts/dur in µs relative to the query start) —
    loadable in Perfetto / chrome://tracing as-is."""
    events: list[dict] = []
    tids = {0: "query"}
    for s in entry["spans"]:
        tids.setdefault(s["tid"], s["thread"])
        ev = {"name": s["name"], "cat": s["cat"], "ph": "X",
              "ts": s["begin_ns"] / 1e3,
              "dur": (s["end_ns"] - s["begin_ns"]) / 1e3,
              "pid": 1, "tid": s["tid"]}
        if s["args"]:
            ev["args"] = dict(s["args"])
        events.append(ev)
    meta = [{"name": "process_name", "ph": "M", "pid": 1,
             "args": {"name": f"serenedb query {entry['trace_id']}"}}]
    for tid, tname in tids.items():
        meta.append({"name": "thread_name", "ph": "M", "pid": 1,
                     "tid": tid, "args": {"name": tname}})
    return {"traceEvents": meta + events, "displayTimeUnit": "ms",
            "otherData": {"trace_id": entry["trace_id"],
                          "query": entry["query"][:500],
                          "begin_epoch_us": entry["begin_epoch_us"],
                          "duration_ms": entry["duration_ns"] / 1e6,
                          "error": entry["error"],
                          "peak_bytes": entry.get("peak_bytes"),
                          "stages_ns": entry.get("stages"),
                          "answered": entry.get("answered"),
                          "spans_dropped": entry["spans_dropped"]}}


def _ms(ns: int) -> str:
    return f"{ns / 1e6:.3f}"


def annotate_plan(plan, profile: QueryProfile, mem=None) -> list[str]:
    """EXPLAIN ANALYZE rendering: the plan tree with PG-style
    `(actual time=first..total rows=N loops=L)` suffixes plus prune /
    device detail lines, and per-operator `Memory: peak=… live=…`
    lines when a MemoryAccountant ran (serene_mem_account). Nodes the
    executor fused away (device offload) render `(never executed)`
    like PG's unvisited branches."""
    from .resources import fmt_kb
    merged = profile.merged()
    mem_merged = mem.merged() if mem is not None else {}

    def mem_line(pad: str, node) -> list[str]:
        m = mem_merged.get(id(node))
        if m is None:
            return []
        live, peak = m
        return [f"{pad}Memory: peak={fmt_kb(peak)} "
                f"live={fmt_kb(max(live, 0))}"]

    def walk(node, depth: int) -> list[str]:
        pad = "  " * depth
        s = merged.get(id(node))
        if s is None:
            lines = [f"{pad}{node.label()} (never executed)"]
            lines.extend(mem_line(pad + "  ", node))
        else:
            first = s.first_ns if s.first_ns is not None else s.wall_ns
            lines = [f"{pad}{node.label()} "
                     f"(actual time={_ms(first)}..{_ms(s.wall_ns)} "
                     f"rows={s.rows_out} loops={max(s.loops, 1)})"]
            detail = pad + "  "
            if s.morsels_scheduled or s.morsels_pruned:
                jf = (f" join_filter_pruned={s.morsels_jf_pruned}"
                      if s.morsels_jf_pruned else "")
                lines.append(
                    f"{detail}Morsels: scheduled={s.morsels_scheduled} "
                    f"zonemap_pruned={s.morsels_pruned}{jf}")
            if s.device_ns or s.device_declined:
                comp = ""
                if s.device_prog_hits or s.device_prog_misses:
                    # any miss means this execution paid (at least one)
                    # XLA compile; all-hit means every program came
                    # from the ledger warm (obs/device.py)
                    comp = " compile=" + \
                        ("miss" if s.device_prog_misses else "hit")
                dec = (f" declined={s.device_declined}"
                       if s.device_declined else "")
                lines.append(
                    f"{detail}Device: time={_ms(s.device_ns)} "
                    f"ms{comp}{dec}")
            if s.batch_queries:
                lines.append(
                    f"{detail}Batch: queries={s.batch_queries} "
                    f"window={_ms(s.batch_window_ns)} ms "
                    f"shared_scoring={_ms(s.batch_scoring_ns)} ms")
            if s.shard_pipelines or s.shard_pruned:
                combine = "device" if s.shard_collective else "host"
                lines.append(f"{detail}Shards: n={s.shard_pipelines} "
                             f"pruned={s.shard_pruned} "
                             f"combine={combine}")
            lines.extend(mem_line(detail, node))
        for c in node.children():
            lines.extend(walk(c, depth + 1))
        return lines

    return walk(plan, 0)


def annotate_plan_json(plan, profile: Optional[QueryProfile],
                       mem=None) -> dict:
    """EXPLAIN (FORMAT JSON) rendering: the plan tree as a
    machine-readable object — PG's JSON key shapes where they map
    ("Node Type", "Actual Total Time", "Actual Rows", "Plans"), plus the
    engine's prune / device / batch / shard detail as flat keys instead
    of the text renderer's detail lines, and per-operator "Peak Memory
    Bytes" / "Live Memory Bytes" when a MemoryAccountant ran.
    profile=None renders structure only (plain EXPLAIN)."""
    merged = profile.merged() if profile is not None else {}
    mem_merged = mem.merged() if mem is not None else {}

    def stamp_mem(out: dict, node) -> None:
        m = mem_merged.get(id(node))
        if m is not None:
            out["Peak Memory Bytes"] = m[1]
            out["Live Memory Bytes"] = max(m[0], 0)

    def walk(node) -> dict:
        out: dict = {"Node Type": node.label()}
        if profile is not None:
            s = merged.get(id(node))
            if s is None:
                out["Never Executed"] = True
                stamp_mem(out, node)
            else:
                first = s.first_ns if s.first_ns is not None else s.wall_ns
                out["Actual Startup Time"] = round(first / 1e6, 3)
                out["Actual Total Time"] = round(s.wall_ns / 1e6, 3)
                out["Actual Rows"] = s.rows_out
                out["Actual Loops"] = max(s.loops, 1)
                if s.morsels_scheduled or s.morsels_pruned:
                    out["Morsels Scheduled"] = s.morsels_scheduled
                    out["Morsels Zonemap Pruned"] = s.morsels_pruned
                    if s.morsels_jf_pruned:
                        out["Morsels Join Filter Pruned"] = \
                            s.morsels_jf_pruned
                if s.device_ns:
                    out["Device Time"] = round(s.device_ns / 1e6, 3)
                    if s.device_prog_hits or s.device_prog_misses:
                        out["Device Compile"] = \
                            "miss" if s.device_prog_misses else "hit"
                if s.device_declined:
                    out["Device Declined"] = s.device_declined
                if s.batch_queries:
                    out["Batch Queries"] = s.batch_queries
                    out["Batch Window Time"] = \
                        round(s.batch_window_ns / 1e6, 3)
                    out["Batch Shared Scoring Time"] = \
                        round(s.batch_scoring_ns / 1e6, 3)
                if s.shard_pipelines or s.shard_pruned:
                    out["Shard Pipelines"] = s.shard_pipelines
                    out["Shard Morsels Pruned"] = s.shard_pruned
                    out["Shard Combine"] = \
                        "device" if s.shard_collective else "host"
                stamp_mem(out, node)
        kids = node.children()
        if kids:
            out["Plans"] = [walk(c) for c in kids]
        return out

    return walk(plan)
