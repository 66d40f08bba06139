"""Fixed registry of atomic gauges, ClickHouse-CurrentMetrics style.

Reference analog: libs/basics/metrics.h:27-71 — relaxed-atomic gauges bumped
only at task/connection boundaries (never per row), surfaced via the
`sdb_metrics` system view. Python ints under a lock are cheap enough at those
boundaries.
"""

from __future__ import annotations

import bisect
import threading
from contextlib import contextmanager


class Gauge:
    __slots__ = ("name", "description", "_value", "_lock")

    def __init__(self, name: str, description: str = ""):
        self.name = name
        self.description = description
        self._value = 0
        self._lock = threading.Lock()

    def add(self, n: int = 1) -> None:
        with self._lock:
            self._value += n

    def sub(self, n: int = 1) -> None:
        self.add(-n)

    def set(self, n: int) -> None:
        """Overwrite the level (byte-size gauges that track a cache's
        current footprint rather than accumulate a count)."""
        with self._lock:
            self._value = n

    def delta(self, baseline: int) -> int:
        """Current value minus a snapshot baseline (one atomic read) —
        the scrape-side pairing of Registry.snapshot()."""
        return self.value - baseline

    @property
    def value(self) -> int:
        with self._lock:
            return self._value

    @contextmanager
    def scoped(self, n: int = 1):
        self.add(n)
        try:
            yield
        finally:
            self.sub(n)


#: log-spaced histogram bucket upper bounds in NANOSECONDS: powers of two
#: from 1 µs to ~137 s (28 buckets) plus the implicit +Inf overflow slot.
#: Log spacing keeps relative quantile error bounded (one octave) across
#: six decades of latency with a fixed, tiny footprint — the Prometheus
#: classic-histogram shape, shared by the process-wide `Histogram` gauges
#: and the per-fingerprint latency sketches in obs/statements.py.
HIST_BOUNDS_NS: tuple[int, ...] = tuple(1000 * (1 << k) for k in range(28))


def hist_bucket_index(ns: int) -> int:
    """Bucket slot for one observation: the first bound >= ns, or the
    +Inf slot (len(HIST_BOUNDS_NS)) past the last finite bound."""
    return bisect.bisect_left(HIST_BOUNDS_NS, max(int(ns), 0))


def hist_quantile_ns(counts, q: float) -> float:
    """Quantile estimate from bucket counts (len = len(HIST_BOUNDS_NS)+1)
    by linear interpolation inside the target bucket — the same estimate
    Prometheus' histogram_quantile() would derive from the exported
    buckets, so /_stats and a real Prometheus agree. Observations in the
    +Inf bucket clamp to the largest finite bound. Returns ns (0.0 when
    the histogram is empty)."""
    total = sum(counts)
    if total <= 0:
        return 0.0
    target = q * total
    cum = 0.0
    for i, c in enumerate(counts):
        if c <= 0:
            continue
        if cum + c >= target:
            if i >= len(HIST_BOUNDS_NS):      # +Inf bucket: clamp
                return float(HIST_BOUNDS_NS[-1])
            lo = float(HIST_BOUNDS_NS[i - 1]) if i else 0.0
            hi = float(HIST_BOUNDS_NS[i])
            return lo + (hi - lo) * ((target - cum) / c)
        cum += c
    return float(HIST_BOUNDS_NS[-1])


class Histogram:
    """Fixed log-spaced-bucket histogram (Prometheus classic histogram
    semantics: cumulative `le` buckets + sum + count).

    Observed at task/statement boundaries only — one bisect over 28
    bounds plus one locked triple update per observation, never per row —
    so p50/p95/p99 become derivable from `/metrics` and `/_stats`
    without any per-request allocation.

    `unit` is "s" (observations in NANOSECONDS, exported as seconds —
    the latency histograms) or "bytes" (observations in bytes, exported
    raw — the memory histograms). The log-spaced bounds read naturally
    in both: 1 µs..137 s, or 1 kB..137 GB."""

    __slots__ = ("name", "description", "unit", "_counts", "_sum_ns",
                 "_lock")

    def __init__(self, name: str, description: str = "", unit: str = "s"):
        self.name = name
        self.description = description
        self.unit = unit
        self._counts = [0] * (len(HIST_BOUNDS_NS) + 1)
        self._sum_ns = 0
        self._lock = threading.Lock()

    def observe_ns(self, ns: int) -> None:
        i = hist_bucket_index(ns)
        with self._lock:
            self._counts[i] += 1
            self._sum_ns += max(int(ns), 0)

    def snapshot(self) -> tuple[list[int], int]:
        """(per-bucket counts, sum ns) under one lock acquisition."""
        with self._lock:
            return list(self._counts), self._sum_ns

    @property
    def count(self) -> int:
        with self._lock:
            return sum(self._counts)

    def quantile_ns(self, q: float) -> float:
        counts, _ = self.snapshot()
        return hist_quantile_ns(counts, q)

    def percentiles_ms(self) -> dict:
        """{count, p50_ms, p95_ms, p99_ms} for the /_stats JSON."""
        counts, _ = self.snapshot()
        return {"count": sum(counts),
                "p50_ms": round(hist_quantile_ns(counts, 0.50) / 1e6, 3),
                "p95_ms": round(hist_quantile_ns(counts, 0.95) / 1e6, 3),
                "p99_ms": round(hist_quantile_ns(counts, 0.99) / 1e6, 3)}

    def reset(self) -> None:
        with self._lock:
            self._counts = [0] * (len(HIST_BOUNDS_NS) + 1)
            self._sum_ns = 0


class Registry:
    def __init__(self):
        self._gauges: dict[str, Gauge] = {}
        self._hists: dict[str, Histogram] = {}

    def gauge(self, name: str, description: str = "") -> Gauge:
        g = self._gauges.get(name)
        if g is None:
            g = self._gauges[name] = Gauge(name, description)
        return g

    def histogram(self, name: str, description: str = "",
                  unit: str = "s") -> Histogram:
        h = self._hists.get(name)
        if h is None:
            h = self._hists[name] = Histogram(name, description, unit)
        return h

    def all(self) -> list[Gauge]:
        return [self._gauges[k] for k in sorted(self._gauges)]

    def all_histograms(self) -> list[Histogram]:
        return [self._hists[k] for k in sorted(self._hists)]

    def snapshot(self) -> dict[str, int]:
        """One point-in-time {name: value} map for scrapes and tests:
        every gauge is read exactly once (each read atomic under its own
        lock), so a consumer iterating the result never races the
        per-gauge locks mid-scrape or sees a gauge twice at two
        values."""
        return {g.name: g.value for g in self.all()}


REGISTRY = Registry()

PG_CONNECTIONS = REGISTRY.gauge("PgConnections", "open PG wire connections")
HTTP_CONNECTIONS = REGISTRY.gauge("HttpConnections", "open HTTP connections")
QUERIES_ACTIVE = REGISTRY.gauge("QueriesActive", "queries currently executing")
REFRESH_ACTIVE = REGISTRY.gauge("RefreshActive", "running refresh tasks")
COMPACTION_ACTIVE = REGISTRY.gauge("CompactionActive", "running compactions")
DEVICE_OFFLOADS = REGISTRY.gauge("DeviceOffloads", "batches dispatched to TPU")
STATEMENTS_ANSWERED_DEVICE = REGISTRY.gauge(
    "StatementsAnsweredDevice",
    "traced non-utility statements whose timeline held a device_enqueue "
    "stage (counted where the request ends; a result-cache hit counts "
    "under neither gauge)")
STATEMENTS_ANSWERED_HOST = REGISTRY.gauge(
    "StatementsAnsweredHost",
    "traced non-utility statements answered without any device program")
DEVICE_BYTES = REGISTRY.gauge("DeviceBytesMoved", "bytes copied host->device")
DEVICE_JOINS_FUSED = REGISTRY.gauge(
    "DeviceJoinsFused",
    "joins executed inside a fused device program: one per join edge "
    "per dispatch (the chain program, exec/device_chain.py, and the "
    "two-table pair-count program)")
HOST_JOINS = REGISTRY.gauge("HostJoins", "host JoinNode executions")
HOST_FLATTENED_JOINS = REGISTRY.gauge(
    "HostFlattenedJoins",
    "host JoinNode executions of a flattened subquery: the semi, anti "
    "or mark join of an EXISTS, IN or NOT IN, or the left join of a "
    "correlated aggregate (sql/decorrelate.py), run on the host")
SUBQUERIES_FLATTENED = REGISTRY.gauge(
    "SubqueriesFlattened",
    "subquery expressions planned as a join (semi, anti, mark, or an "
    "aggregate grouped by the correlation keys and joined back) or, "
    "uncorrelated under a comparison, computed once into a constant")
SUBQUERIES_PER_ROW = REGISTRY.gauge(
    "SubqueriesPerRow",
    "correlated subquery expressions bound to run once per distinct "
    "outer key (the literal-substitution path)")
DEVICE_REDUCTIONS_FUSED = REGISTRY.gauge(
    "DeviceReductionsFused",
    "flattened subqueries executed inside a join chain program: one per "
    "reduction edge (a sub-chain reduced into one relation's rows) or "
    "semi join over a key its build side is unique on, per dispatch")
DEVICE_JOIN_BYTES = REGISTRY.gauge(
    "DeviceJoinBytes",
    "per fused join dispatch, the bytes the statement has to read "
    "whatever implements it: for every table it references, rows x the "
    "narrowest 1/2/4/8-byte integer width of each column it references "
    "(a string column as its code)")
DEVICE_JOIN_INDEX_BUILDS = REGISTRY.gauge(
    "DeviceJoinIndexBuilds",
    "join row indexes built (a chain program's per-edge key->row "
    "lookups, once per pair of table publications)")
DEVICE_JOIN_INDEX_BYTES = REGISTRY.gauge(
    "DeviceJoinIndexBytes",
    "bytes of join row indexes resident (set, not summed)")
DEVICE_CHAIN_COMPACTED = REGISTRY.gauge(
    "DeviceChainCompacted",
    "join chain dispatches into many groups whose surviving rows fit a "
    "rung of the compaction ladder: only they were scattered")
DEVICE_CHAIN_SCATTERED_FULL = REGISTRY.gauge(
    "DeviceChainScatteredFull",
    "join chain dispatches into many groups whose surviving rows passed "
    "the ladder's last rung: every probe row was scattered")
DEVICE_CACHE_HITS = REGISTRY.gauge(
    "DeviceCacheHits",
    "a device-resident column was asked for and found in HBM (no "
    "host->device transfer), whichever cache holds it: DEVICE_CACHE "
    "(fused join tier) or a provider's own residency cache (device "
    "aggregates, top-N, zone-map ranges)")
DEVICE_CACHE_MISSES = REGISTRY.gauge(
    "DeviceCacheMisses",
    "a device-resident column was asked for and had to be uploaded")
DEVICE_CACHE_EVICTIONS = REGISTRY.gauge(
    "DeviceCacheEvictions",
    "device column cache entries dropped (LRU past the byte cap or a "
    "superseded publication swept on store)")
DEVICE_CACHE_BYTES = REGISTRY.gauge(
    "DeviceCacheBytes",
    "current bytes held by the device column cache")
DEVICE_PROGRAMS_COMPILED = REGISTRY.gauge(
    "DeviceProgramsCompiled",
    "jitted device programs built by the compile ledger "
    "(obs/device.py) — each is one XLA trace+compile on first dispatch")
DEVICE_PROGRAM_HITS = REGISTRY.gauge(
    "DeviceProgramCacheHits",
    "compile-ledger probes served by an already-compiled program "
    "(no retrace, no recompile)")
DEVICE_PROGRAM_MISSES = REGISTRY.gauge(
    "DeviceProgramCacheMisses",
    "compile-ledger probes that had to build a new program")
DEVICE_PROGRAM_EVICTIONS = REGISTRY.gauge(
    "DeviceProgramCacheEvictions",
    "compiled programs dropped by the bounded program LRU "
    "(serene_program_cache_entries); an evicted shape re-compiles on "
    "next use")
DEVICE_PROGRAM_ENTRIES = REGISTRY.gauge(
    "DeviceProgramCacheEntries",
    "compiled programs currently held by the program LRU (live)")
DEVICE_RECOMPILE_STORMS = REGISTRY.gauge(
    "DeviceRecompileStorms",
    "recompile-storm warnings fired: one program family compiled more "
    "than RECOMPILE_STORM_PER_MIN new shapes within a minute — repeat "
    "queries are not reusing cached executables")
DEVICE_TRANSFERS_UP = REGISTRY.gauge(
    "DeviceTransfersUp",
    "host->device transfers recorded by the device telemetry ledger "
    "(column uploads, code/rowmask tiles, stacked mesh commits, "
    "cached build-output commits)")
DEVICE_FETCH_BYTES = REGISTRY.gauge(
    "DeviceBytesFetched",
    "bytes copied device->host fetching program outputs (the "
    "readback sibling of DeviceBytesMoved)")
WAL_COMMITS = REGISTRY.gauge("WalCommits", "search WAL commit records written")
WAL_FSYNCS = REGISTRY.gauge(
    "WalFsyncs", "WAL group-commit fsync calls (commits per fsync = "
    "WalCommits / WalFsyncs — the group-commit amortization ratio)")
INGEST_DOCS = REGISTRY.gauge(
    "IngestDocs", "rows appended through the write path (INSERT/COPY)")
INGEST_BYTES = REGISTRY.gauge(
    "IngestBytes", "columnar bytes appended through the write path")
INGEST_BATCHES = REGISTRY.gauge(
    "IngestBatches", "write-path append batches (statements or COPY "
    "chunks; IngestDocs / IngestBatches = mean batch size)")
SEGMENT_BUILDS = REGISTRY.gauge(
    "SegmentBuilds", "inverted-index field segments built (initial "
    "builds + delta tails)")
SEGMENT_MERGES = REGISTRY.gauge(
    "SegmentMerges", "tiered segment merges (adjacent runs compacted "
    "into one segment)")
NATIVE_INDEX_BUILDS = REGISTRY.gauge(
    "NativeIndexBuilds", "field-index chunks tokenized by the native "
    "C++ one-pass indexer")
NATIVE_INDEX_FALLBACKS = REGISTRY.gauge(
    "NativeIndexFallbacks", "native-eligible field-index chunks that "
    "fell back to the Python tokenizer because the native library "
    "could not be built or loaded")
POOL_MORSELS = REGISTRY.gauge("PoolMorselsExecuted",
                              "morsel tasks executed by the worker pool")
POOL_QUEUE_WAIT_US = REGISTRY.gauge("PoolQueueWaitUs",
                                    "cumulative µs tasks waited queued")
POOL_BUSY_US = REGISTRY.gauge("PoolBusyUs",
                              "cumulative µs workers spent running tasks")
POOL_STEALS = REGISTRY.gauge("PoolSteals",
                             "tasks stolen from a sibling worker's deque")
ZONEMAP_PRUNED = REGISTRY.gauge(
    "ZonemapMorselsPruned",
    "scan/aggregate morsels skipped because block statistics proved no "
    "row could match")
ZONEMAP_SCANNED = REGISTRY.gauge(
    "ZonemapMorselsScanned",
    "morsels that passed zone-map analysis and were actually scanned")
JOIN_FILTER_PRUNED = REGISTRY.gauge(
    "JoinFilterMorselsPruned",
    "probe-side scan morsels skipped because the build side's published "
    "key range proved no row of the block could find a join partner")
JOIN_FILTER_SCANNED = REGISTRY.gauge(
    "JoinFilterMorselsScanned",
    "probe-side morsels that passed the join-filter key-range analysis "
    "and were actually scanned")
ZONEMAP_STALE_REBUILDS = REGISTRY.gauge(
    "ZonemapStaleRebuilds",
    "zone-map column stats rebuilt from scratch after a non-append "
    "mutation invalidated the cached version")
QUERIES_EXECUTED = REGISTRY.gauge(
    "QueriesExecuted", "statements completed (success) since start")
SLOW_QUERIES = REGISTRY.gauge(
    "SlowQueries",
    "statements that exceeded serene_log_min_duration_ms and were "
    "written to the slow-query log")
RESULT_CACHE_HITS = REGISTRY.gauge(
    "ResultCacheHits",
    "statements served from the result cache without executing")
RESULT_CACHE_MISSES = REGISTRY.gauge(
    "ResultCacheMisses",
    "cacheable statements that executed because no entry matched")
RESULT_CACHE_EVICTIONS = REGISTRY.gauge(
    "ResultCacheEvictions",
    "result-cache entries evicted (LRU byte pressure or a superseded "
    "publication swept)")
RESULT_CACHE_BYTES = REGISTRY.gauge(
    "ResultCacheBytes", "bytes currently held by the result cache")
FRAGMENT_CACHE_HITS = REGISTRY.gauge(
    "FragmentCacheHits",
    "per-segment search fragments (filter doc sets / top-k outputs) "
    "served from the fragment cache")
FRAGMENT_CACHE_MISSES = REGISTRY.gauge(
    "FragmentCacheMisses",
    "per-segment search fragments computed because no entry matched")
FRAGMENT_CACHE_BYTES = REGISTRY.gauge(
    "FragmentCacheBytes", "bytes currently held by the fragment cache")
SEARCH_BATCH_DISPATCHES = REGISTRY.gauge(
    "SearchBatchDispatches",
    "coalesced search scoring dispatches executed by the query batcher "
    "(each scores one or more top-k queries in one vectorized pass)")
SEARCH_BATCH_QUERIES = REGISTRY.gauge(
    "SearchBatchQueries",
    "top-k queries scored through batcher dispatches (QUERIES / "
    "DISPATCHES = mean batch size)")
SEARCH_BATCH_COALESCED = REGISTRY.gauge(
    "SearchBatchCoalesced",
    "queries that shared their scoring dispatch with at least one other "
    "query (the batching win; singleton dispatches don't count)")
SEARCH_QUERIES_SCORED_DEVICE = REGISTRY.gauge(
    "SearchQueriesScoredDevice",
    "top-k queries whose top-k came out of a device scoring program "
    "(bm25_accumulate + bm25_topk, dense_topk, the mesh kernel) in at "
    "least one segment; with SearchQueriesScoredHost a partition of the "
    "queries scored (a fragment-cache hit is neither)")
SEARCH_QUERIES_SCORED_HOST = REGISTRY.gauge(
    "SearchQueriesScoredHost",
    "top-k queries whose top-k came out of a host tier in every "
    "segment: _cpu_score over MaxScore candidates or an exact-match "
    "rescore, or no scoring at all (no term of the query is indexed)")
SEARCH_QUERIES_TERM, SEARCH_QUERIES_UNION, SEARCH_QUERIES_CONJUNCTION, \
    SEARCH_QUERIES_PHRASE = (REGISTRY.gauge(
        "SearchQueries" + shape,
        "`_search` requests whose `query` is " + what + "; the four "
        "shapes partition the requests whose query is a match or a "
        "match_phrase (server/es_api.py: search)")
        for shape, what in (
            ("Term", "a match or match_phrase of one word"),
            ("Union", "a match of several words, operator or"),
            ("Conjunction", "a match of several words, operator and"),
            ("Phrase", "a match_phrase of several words")))
SEARCH_REQUESTS_COUNT_ONLY, SEARCH_REQUESTS_HITS_ONLY, \
    SEARCH_REQUESTS_HITS_AND_COUNT = (REGISTRY.gauge(
        "SearchRequests" + asked,
        "`_search` requests with a query that asked for " + what + "; a "
        "partition of those that asked for anything")
        for asked, what in (
            ("CountOnly", "the exact total and no hits (size 0)"),
            ("HitsOnly", "hits and no total (track_total_hits false)"),
            ("HitsAndCount", "hits and the exact total")))
SEARCH_PHRASE_CANDIDATES = REGISTRY.gauge(
    "SearchPhraseCandidates",
    "documents that hold every term of a phrase whose positional join "
    "ran: what the join read positions of")
SEARCH_PHRASE_MATCHES = REGISTRY.gauge(
    "SearchPhraseMatches",
    "documents those joins found to hold the phrase")
SEARCH_PHRASE_RESCORED = REGISTRY.gauge(
    "SearchPhraseRescored",
    "phrase top-k queries whose device top-k held a document outside "
    "the phrase's match set and were scored again by _cpu_score; a "
    "phrase of plain terms with slop 0 never is (its match set bounds "
    "the top-k inside the program), one per segment")
SEARCH_POSTINGS_DISPATCHED = REGISTRY.gauge(
    "SearchPostingsDispatched",
    "valid (non-padding) postings in the block rows and light-term "
    "tails handed to device scoring programs after pruning; the dense "
    "path counts the document frequencies of the rows it gathers")
SEARCH_POSTINGS_RESIDENT = REGISTRY.gauge(
    "SearchPostingsResident",
    "the postings, within SearchPostingsDispatched, whose term's whole "
    "saturation row was added to the score plane in place of a "
    "scatter: a resident row of the plane kernel, or a row of the "
    "dense path's matrix")
SEARCH_PROGRAMS_PREBUILT = REGISTRY.gauge(
    "SearchProgramsPrebuilt",
    "scoring programs built by an index build or refresh before the "
    "index answered a search (SegmentSearcher.prebuild)")
SEARCH_COUNT_BITSET = REGISTRY.gauge(
    "SearchCountBitset",
    "exact totals of a search answered by OR-ing doc bitsets "
    "(SegmentSearcher.count_filter: the query is a union of posting "
    "lists), one per segment asked; with SearchCountIntersected and "
    "SearchCountMaterialized a partition of the segment-level counts")
SEARCH_COUNT_MATERIALIZED = REGISTRY.gauge(
    "SearchCountMaterialized",
    "exact totals of a search taken as the length of the sorted doc "
    "set: built for the count (negations, nested booleans, sloppy and "
    "synonym phrases) or found in the fragment cache; one per segment "
    "asked")
SEARCH_COUNT_INTERSECTED = REGISTRY.gauge(
    "SearchCountIntersected",
    "exact totals of a conjunction of terms (the dense terms' doc "
    "bitsets AND-ed, the sparse terms' lists intersected rarest first "
    "and probed against them) or of a phrase of plain terms (the size "
    "of its positional join); one per segment asked. With "
    "SearchCountBitset and SearchCountMaterialized a partition of the "
    "segment-level counts")
SEARCH_COUNT_BITSET_BYTES = REGISTRY.gauge(
    "SearchCountBitsetBytes",
    "bytes of dense-term doc bitsets built for count_filter (a term "
    "whose bitset is no larger than its posting list keeps one for the "
    "segment's life); accumulates over every segment built")
SEARCH_POSTING_LENGTH_BYTES = REGISTRY.gauge(
    "SearchPostingLengthBytes",
    "HBM bytes of the per-posting document lengths of the resident "
    "posting stores (BlockStore.block_dls + raw_dls: what the BM25 "
    "accumulate step reads by row in place of a gather from the norms "
    "table); falls when a segment's store is released")
SEARCH_RESIDENT_ROW_BYTES = REGISTRY.gauge(
    "SearchResidentRowBytes",
    "HBM bytes of the saturation rows held resident (ops/bm25.py "
    "DenseStore: a store's dense terms for the plane kernel, or every "
    "term for the dense path; at most DENSE_HBM_BUDGET a matrix); falls "
    "when a matrix is released")
VECTOR_SEARCH_QUERIES = REGISTRY.gauge(
    "VectorSearchQueries",
    "knn / MaxSim queries scored by the vector subsystem "
    "(search/vector_store.py) — each member of a coalesced batch "
    "counts once")
VECTOR_SEARCH_DISPATCHES = REGISTRY.gauge(
    "VectorSearchDispatches",
    "jitted vector programs dispatched (probe, brute-oracle and MaxSim "
    "batches each count one; a warm coalesced batch is exactly one)")
VECTOR_PROBED_CLUSTERS = REGISTRY.gauge(
    "VectorProbedClusters",
    "IVF cluster lists probed across all vector queries (queries x "
    "effective nprobe) — the work that scales with nprobe, not N")
VECTOR_QUERIES_SCORED_FLAT = REGISTRY.gauge(
    "VectorQueriesScoredFlat",
    "knn queries scored by the exact flat scan (family knn_flat_scan: "
    "every row of a flat index read once a dispatch); with "
    "VectorQueriesScoredProbe a partition of the knn queries scored")
VECTOR_QUERIES_SCORED_PROBE = REGISTRY.gauge(
    "VectorQueriesScoredProbe",
    "knn queries scored by the IVF cluster probe (vector_probe) or the "
    "MaxSim program")
VECTOR_ROWS_SCANNED = REGISTRY.gauge(
    "VectorRowsScanned",
    "rows a flat-scan dispatch read from the resident segment, counted "
    "once per DISPATCH, not per query: the bytes knn_roofline divides "
    "by the HBM peak are these rows x dims x 4")
VECTOR_PROGRAMS_PREBUILT = REGISTRY.gauge(
    "VectorProgramsPrebuilt",
    "flat-scan programs (one per batch rung) built by CREATE INDEX or a "
    "refresh before the index answered a search "
    "(SearchProgramsPrebuilt's sibling)")
VECTOR_BYTES_RESIDENT = REGISTRY.gauge(
    "VectorBytesResident",
    "bytes of device memory the vector pool's resident segments hold: "
    "live pages x page size of the paged region (IVF, MaxSim) plus the "
    "unpadded arrays of flat segments")
VECTOR_POOL_HITS = REGISTRY.gauge(
    "VectorPoolHits",
    "vector-pool segment lookups served by pages already resident in "
    "the device region — a hit means the batch re-scored vectors "
    "without re-uploading them")
VECTOR_POOL_MISSES = REGISTRY.gauge(
    "VectorPoolMisses",
    "vector-pool segment lookups that allocated and wrote fresh pages "
    "(first touch of a segment, or re-entry after eviction)")
VECTOR_POOL_EVICTIONS = REGISTRY.gauge(
    "VectorPoolEvictions",
    "resident vector segments evicted LRU from the vector pool to make "
    "room under the serene_vector_pages budget")
SHARD_PIPELINES = REGISTRY.gauge(
    "ShardPipelines",
    "per-shard pipeline executions launched by the sharded execution "
    "tier (serene_shards > 1): each morsel group, fused device dispatch "
    "or segment-set search run over one shard counts once")
SHARD_MORSELS_PRUNED = REGISTRY.gauge(
    "ShardMorselsPruned",
    "probe-side blocks pruned by the shard-to-shard join filter: the "
    "build side's PER-SHARD key min/max ranges proved no row of the "
    "block can find a partner in any build shard")
SHARD_BYTES_SKIPPED = REGISTRY.gauge(
    "ShardBytesSkipped",
    "host->device upload bytes skipped because per-shard pruning "
    "proved a probe shard's blocks partner-less before any transfer")
COLLECTIVE_DISPATCHES = REGISTRY.gauge(
    "CollectiveDispatches",
    "shard_map-partitioned collective dispatches executed by the "
    "sharded tier with serene_shard_combine=device: each fused "
    "join/aggregate (psum/pmin/pmax cross-shard reduction) or search "
    "top-k merge (per-shard sort + all_gather) over the mesh data axis "
    "counts once — the single dispatch that replaces build+N probe "
    "dispatches plus the host-side numpy combine")
COLLECTIVE_COMBINE_NS = REGISTRY.gauge(
    "CollectiveCombineNs",
    "cumulative ns spent inside collective shard-combine dispatches "
    "(the in-program psum/pmin/pmax/all_gather sections, wall time of "
    "the whole one-dispatch program)")
POOL_QUEUE_DEPTH = REGISTRY.gauge(
    "PoolQueueDepth",
    "tasks currently queued in the worker pool (submitted, not yet "
    "picked up) — the live backpressure signal admission control reads")
POOL_RUNNING = REGISTRY.gauge(
    "PoolRunningTasks",
    "tasks currently executing on worker-pool threads")
POOL_TASK_WAIT_NS = REGISTRY.gauge(
    "PoolTaskWaitNs",
    "cumulative ns tasks spent queued before a worker picked them up "
    "(the ns-precision sibling of PoolQueueWaitUs)")
ADMISSION_QUEUED = REGISTRY.gauge(
    "AdmissionQueued",
    "statements that had to WAIT in the admission queue before "
    "executing (cumulative; sched/governor.py)")
ADMISSION_REJECTED = REGISTRY.gauge(
    "AdmissionRejected",
    "statements rejected with SQLSTATE 53300 because the admission "
    "queue was already serene_admission_queue_depth deep")
ADMISSION_WAIT_NS = REGISTRY.gauge(
    "AdmissionWaitNs",
    "cumulative ns statements spent queued for admission before "
    "starting (the statement-level sibling of PoolTaskWaitNs)")
ADMISSION_QUEUE_DEPTH = REGISTRY.gauge(
    "AdmissionQueueDepth",
    "statements currently waiting in the admission queue (live)")
CONNECTIONS_OPEN = REGISTRY.gauge(
    "ConnectionsOpen",
    "sockets currently open on the serving front door, both protocols "
    "(sched/governor.py ConnectionGate; server/frontdoor.py)")
CONNECTIONS_IDLE = REGISTRY.gauge(
    "ConnectionsIdle",
    "front-door connections waiting for the client's next request / "
    "command (live)")
CONNECTIONS_ACTIVE = REGISTRY.gauge(
    "ConnectionsActive",
    "front-door connections with a request or handshake in flight "
    "(live)")
CONNECTIONS_REJECTED = REGISTRY.gauge(
    "ConnectionsRejected",
    "connections rejected at the accept gate because "
    "serene_max_connections sockets were already open (cumulative; "
    "pgwire clients get a clean 53300 error packet, HTTP clients a "
    "429, both before a single byte of the session is parsed)")
SOCKET_BYTES_BUFFERED = REGISTRY.gauge(
    "SocketBytesBuffered",
    "bytes sitting in front-door transport write buffers (slow "
    "readers), sampled at scrape time; bounded per connection by "
    "serene_conn_write_high_kb + pause_reading")
SCHED_PREEMPTIONS = REGISTRY.gauge(
    "SchedPreemptions",
    "fair-share pool picks that ran a later-submitted statement's task "
    "ahead of the FIFO-oldest queued task (each one is an interleave "
    "plain FIFO would not have done; serene_fair_share)")
TRACES_RECORDED = REGISTRY.gauge(
    "TracesRecorded",
    "query timelines finalized into the flight recorder since start")
TRACE_SPANS_DROPPED = REGISTRY.gauge(
    "TraceSpansDropped",
    "span events dropped because a per-thread trace ring hit its cap "
    "(the timeline stays bounded; widest spans are still present)")
MEM_ACCOUNT_EVENTS = REGISTRY.gauge(
    "MemAccountEvents",
    "charge/release events recorded by per-query memory accounting "
    "(serene_mem_account)")
PROCESS_RSS_BYTES = REGISTRY.gauge(
    "ProcessRssBytes",
    "resident set size of this process (/proc/self/statm), sampled at "
    "scrape time and by the maintenance ticker")
PROCESS_UPTIME_SECONDS = REGISTRY.gauge(
    "ProcessUptimeSeconds",
    "seconds since this process initialized the metrics registry")
GC_GEN0_COLLECTIONS = REGISTRY.gauge(
    "GcGen0Collections", "CPython gc generation-0 collections")
GC_GEN1_COLLECTIONS = REGISTRY.gauge(
    "GcGen1Collections", "CPython gc generation-1 collections")
GC_GEN2_COLLECTIONS = REGISTRY.gauge(
    "GcGen2Collections", "CPython gc generation-2 collections")

#: latency histograms (log-spaced buckets; Prometheus histogram series
#: in /metrics, p50/p95/p99 in /_stats). Observed at statement / task /
#: dispatch boundaries only.
QUERY_LATENCY_HIST = REGISTRY.histogram(
    "QueryLatency",
    "end-to-end statement latency (success paths)")
REQUEST_LATENCY_HIST = REGISTRY.histogram(
    "RequestLatency",
    "one traced request from receipt of its message at the front door "
    "(or the engine call, without one) to the response's last byte "
    "handed to the transport; QueryLatency is the statement inside it")
#: the request's timeline by stage (obs/trace.py: STAGES): one
#: observation per request = the stage's summed time in it, observed
#: only when the stage occurred; per request they add up to
#: RequestLatency exactly
STAGE_HISTS = {name: REGISTRY.histogram(hist, desc) for name, hist, desc in (
    ("fd_parse", "StageParse",
     "parser.parse at the front door; of a `_search`, the body's JSON, "
     "the DSL's translation and its SQL texts' parse"),
    ("fd_queue", "StageFdQueue",
     "front-door thread handoffs: run_in_executor submit -> callable "
     "starts, callable done -> the session coroutine resumes"),
    ("fd_encode", "StageFdEncode",
     "wire encoding and flush of the response; of a `_search`, the "
     "hits' assembly from the result rows"),
    ("cache_probe", "StageCacheProbe",
     "result-cache digest, lookups and store"),
    ("plan", "StagePlan", "bind, plan and search rewrite"),
    ("device_prepare", "StageDevicePrepare",
     "host work before a device program runs: admission, pin, "
     "factorize, key planning, residency lookup / upload, program "
     "lookup"),
    ("device_upload", "StageDeviceUpload",
     "a program call's host operands (numpy arrays, scalars) committed "
     "to the device before the call, and the mesh scorer's sections"),
    ("device_enqueue", "StageDeviceEnqueue",
     "the jitted call returning, on device-resident operands only "
     "(first call: trace + compile)"),
    ("device_wait", "StageDeviceWait",
     "the blocking readback: device execution + device->host copy"),
    ("device_finalize", "StageDeviceFinalize",
     "host decode of the program's outputs into the result batch"),
    ("host_scan", "StageHostScan",
     "predicates and projections evaluated on the host, batch by batch "
     "(scan filter, Filter, Project)"),
    ("host_concat", "StageHostConcat",
     "concat_batches: the copy of every column and the merge of string "
     "columns' distinct dictionaries"),
    ("host_group", "StageHostGroup",
     "host hash-aggregate, factorize and distinct finalize"),
    ("host_sort", "StageHostSort", "the materializing host sort"),
    ("host_join", "StageHostJoin",
     "a host JoinNode's own work: key match, residual, null extension "
     "and the gather of both sides (its inputs' scans are their own)"),
    ("batch_wait", "StageBatchWait",
     "a top-k query waiting in the search batcher: submission until the "
     "dispatch that carries it starts, and, for a member another thread "
     "dispatched, the end of that dispatch until its own thread resumes"),
    ("search_plan", "StageSearchPlan",
     "host planning of a scoring dispatch: query shapes, block-max WAND "
     "plans, MaxScore candidates, batch assembly and packing, the "
     "accumulate steps' buffers (query_chunks), doc masks and the "
     "dense steps' slot fill"),
    ("search_phrase", "StageSearchPhrase",
     "the positional join of a phrase: the (document, position) keys of "
     "its slots intersected over the documents that hold all its terms "
     "(SegmentSearcher._phrase_docs); once per request and segment"),
    ("search_host_score", "StageSearchHostScore",
     "host scoring: _cpu_score over candidates, exact-match masks and "
     "the result postprocessing of a scoring dispatch"),
    ("other", "StageOther",
     "the request's time under no stage: RequestLatency minus the "
     "union of its stages"))}
#: how often merge_dictionaries (columnar/column.py) takes which way
HOST_CONCAT_DICT_SHARED = REGISTRY.gauge(
    "HostConcatDictShared",
    "string columns put on one code space with no re-encode: every "
    "piece held the same dictionary object (slices of one table column)")
HOST_CONCAT_DICT_MERGED = REGISTRY.gauge(
    "HostConcatDictMerged",
    "distinct dictionary objects cast, merged and remapped because the "
    "pieces of a string column brought more than one")
#: which way COUNT / SUM / AVG(DISTINCT x) deduplicated on the host
#: (exec/plan.py: `_ScalarAcc`, `_cpu_group_distinct`), one per aggregate
#: that saw a non-NULL value
HOST_DISTINCT_SORTED = REGISTRY.gauge(
    "HostDistinctSorted",
    "host DISTINCT aggregates answered from sorted typed arrays: the "
    "argument is a fixed-width integer array (integers, bool, date, "
    "timestamp), deduplicated by one sort and a comparison of neighbours")
HOST_DISTINCT_OBJECTS = REGISTRY.gauge(
    "HostDistinctObjects",
    "host DISTINCT aggregates that went through Python objects (scalar: "
    "a set over to_pylist) or the generic two-key lexsort (grouped): "
    "float and string arguments")
#: which form the grouped count / DISTINCT presence reductions of the
#: `device_agg` programs took (ops/agg.py: `hist_form`), one per reduction
#: of every statement the family answered
DEVICE_AGG_HISTOGRAM = REGISTRY.gauge(
    "DeviceAggHistogram",
    "grouped count / presence reductions dispatched as the tiled MXU "
    "histogram: the padded cell count was at most HIST_MAX_CELLS")
DEVICE_AGG_SCATTER = REGISTRY.gauge(
    "DeviceAggScatter",
    "grouped count / presence reductions dispatched as the serial "
    "scatter: a cell count past HIST_MAX_CELLS, or a backend that does "
    "not lower the histogram kernel")
POOL_QUEUE_WAIT_HIST = REGISTRY.histogram(
    "PoolQueueWait",
    "per-task worker-pool queue wait (submit -> pickup)")
ACCEPT_QUEUE_WAIT_HIST = REGISTRY.histogram(
    "AcceptQueueWait",
    "per-connection wait between the OS handing the front door a "
    "socket and the session coroutine starting to serve it (event-loop "
    "accept backlog; server/frontdoor.py)")
SEARCH_BATCH_WINDOW_HIST = REGISTRY.histogram(
    "SearchBatchWindow",
    "per-query search-batcher coalescing wait (submit -> dispatch "
    "start)")
DEVICE_DISPATCH_HIST = REGISTRY.histogram(
    "DeviceDispatch",
    "per-offload device time, one meaning at every site: from the "
    "start of the program call (enqueue) to the end of the blocking "
    "readback of its outputs; a chained stage that leaves its outputs "
    "in HBM is observed by the stage that reads them back")
DEVICE_ENQUEUE_CALL_HIST = REGISTRY.histogram(
    "DeviceEnqueueCall",
    "what one call of a jitted program costs the host: its host "
    "operands' commit (`device_upload`) plus the call returning "
    "(`device_enqueue`); one observation per call whatever the tracing "
    "switches and however many requests a coalesced dispatch carries; "
    "a first call is DeviceCompile's")
DEVICE_COMPILE_HIST = REGISTRY.histogram(
    "DeviceCompile",
    "first-dispatch latency of each jitted device program (XLA "
    "trace + compile + the first execution — the compile-stall a "
    "cold query pays; warm dispatches land in DeviceDispatch)")
WAL_FSYNC_HIST = REGISTRY.histogram(
    "WalFsync",
    "WAL group-commit flush+fsync latency (one observation per fsync, "
    "however many commit frames it covered)")
QUERY_PEAK_BYTES_HIST = REGISTRY.histogram(
    "QueryPeakBytes",
    "per-statement accounted peak memory (serene_mem_account): the "
    "sum of per-thread peak live bytes charged at materialization "
    "sites — an upper bound on the statement's true simultaneous peak",
    unit="bytes")
