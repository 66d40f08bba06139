#!/usr/bin/env bash
# Tier-1 verification (ROADMAP.md) plus structural/parity passes.
#
# Pass 1 is the canonical tier-1 suite. Pass 2 re-runs the zone-map and
# morsel parity suites with SERENE_ZONEMAP_VERIFY=1 (tests/conftest.py
# arms the serene_zonemap_verify global): every morsel the zone maps
# prune is re-scanned with the real predicate, so block-statistics/data
# divergence fails the run loudly instead of hiding behind whatever
# queries happened to sample the stale blocks.
set -o pipefail
cd "$(dirname "$0")/.."

rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'not slow' --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log \
    | tr -cd . | wc -c)

echo "== zone-map structural verification pass (serene_zonemap_verify=on) =="
timeout -k 10 300 env JAX_PLATFORMS=cpu SERENE_ZONEMAP_VERIFY=1 \
    python -m pytest tests/test_zonemap.py tests/test_parallel_exec.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly
rc2=$?

# Pass 3 mirrors pass 2 for the join filter: the sideways min/max
# pushdown is forced ON with the zone-map verifier armed, so every
# probe morsel the build-key range prunes is re-scanned with the real
# conjuncts — a range/stats divergence fails the join parity suite
# loudly instead of silently dropping matched rows.
echo "== join-filter structural verification pass (serene_join_filter=on) =="
timeout -k 10 300 env JAX_PLATFORMS=cpu SERENE_JOIN_FILTER=on \
    SERENE_ZONEMAP_VERIFY=1 \
    python -m pytest tests/test_join_exec.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly
rc3=$?

# Pass 4 is the profiler parity leg: the per-operator span collector is
# forced ON (the conftest env hook arms the serene_profile global) over
# the profiler suite plus the morsel/join parity suites, proving the
# instrumentation observes without changing a single result bit at any
# worker count.
echo "== profiler parity pass (serene_profile=on) =="
timeout -k 10 300 env JAX_PLATFORMS=cpu SERENE_PROFILE=on \
    python -m pytest tests/test_profile.py tests/test_parallel_exec.py \
    tests/test_join_exec.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly
rc4=$?

# Pass 5 is the result-cache parity leg: both cache tiers are forced ON
# (the conftest env hook arms the serene_result_cache global) over the
# cache suite plus the morsel/join parity suites — repeat statements
# serve from cache in those suites, so a single stale or perturbed bit
# fails the parity assertions loudly.
echo "== result-cache parity pass (serene_result_cache=on) =="
timeout -k 10 300 env JAX_PLATFORMS=cpu SERENE_RESULT_CACHE=on \
    python -m pytest tests/test_result_cache.py tests/test_parallel_exec.py \
    tests/test_join_exec.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly
rc5=$?

# Pass 6 is the fused-device-pipeline parity leg: the fused tier is
# forced OFF globally (the conftest env hook arms serene_device_fused)
# over the device parity suites plus the join parity suite — proving
# the one-dispatch tier is an optimization layer only: every result is
# bit-identical with it dark, and the suites' own differential tests
# still exercise both paths via their explicit session SETs.
echo "== fused device pipeline parity pass (serene_device_fused=off) =="
timeout -k 10 300 env JAX_PLATFORMS=cpu SERENE_DEVICE_FUSED=off \
    python -m pytest tests/test_device_pipeline.py tests/test_device_agg.py \
    tests/test_join_exec.py -q \
    -p no:cacheprovider -p no:xdist -p no:randomly
rc6=$?

# Pass 7 is the search-batch parity leg: the query batcher is forced
# OFF globally (the conftest env hook arms serene_search_batch) over the
# search, search-batch, and ES API suites — proving batched ragged
# serving is a dispatch-coalescing layer only: every per-query result is
# bit-identical with serial dispatch, and the suites' own parity
# matrices still exercise both modes via their explicit session SETs.
echo "== search-batch parity pass (serene_search_batch=off) =="
timeout -k 10 300 env JAX_PLATFORMS=cpu SERENE_SEARCH_BATCH=off \
    python -m pytest tests/test_search_batch.py tests/test_search.py \
    tests/test_search_regressions.py tests/test_es_api.py \
    tests/test_search_programs.py -q \
    -m 'not slow' -p no:cacheprovider -p no:xdist -p no:randomly
rc7=$?

# Pass 8 is the sharded-execution parity leg: serene_shards is forced
# to 4 globally (the conftest env hook arms the global) over the shard,
# parallel, join, device, and search parity suites — every morsel
# pipeline, fused device dispatch, and multi-segment search then runs
# through per-shard pipelines with cross-shard combiners, and a single
# diverged bit fails the suites' parity assertions loudly.
echo "== sharded execution parity pass (serene_shards=4) =="
timeout -k 10 600 env JAX_PLATFORMS=cpu SERENE_SHARDS=4 \
    python -m pytest tests/test_shard_exec.py tests/test_parallel_exec.py \
    tests/test_join_exec.py tests/test_device_pipeline.py \
    tests/test_search.py -q \
    -m 'not slow' -p no:cacheprovider -p no:xdist -p no:randomly
rc8=$?

# Pass 9 is the timeline-tracing parity leg: serene_trace is forced ON
# globally (the conftest env hook arms the global) over the trace,
# profiler, parallel, shard and search-batch suites — every statement
# then records span timelines (pool queue waits, coalesced-batch
# fan-out, per-shard pipelines, device phases) into the flight recorder
# while the suites' parity matrices assert results stay bit-identical.
echo "== timeline tracing parity pass (serene_trace=on) =="
timeout -k 10 600 env JAX_PLATFORMS=cpu SERENE_TRACE=on \
    python -m pytest tests/test_trace.py tests/test_profile.py \
    tests/test_parallel_exec.py tests/test_shard_exec.py \
    tests/test_search_batch.py -q \
    -m 'not slow' -p no:cacheprovider -p no:xdist -p no:randomly
rc9=$?

# Pass 10 is the multichip in-program-combine parity leg: the sharded
# tier is forced to 4 shards WITH serene_shard_combine=device (the
# conftest env hook arms both globals) over the multichip, shard,
# device and search parity suites — every sharded fused join/aggregate
# then runs as ONE shard_map collective dispatch (psum/pmin/pmax in
# HBM) and every sharded search merge as an in-program all_gather hop,
# and a single diverged bit fails the suites' parity assertions loudly.
echo "== multichip in-program combine parity pass (serene_shard_combine=device) =="
timeout -k 10 600 env JAX_PLATFORMS=cpu SERENE_SHARDS=4 \
    SERENE_SHARD_COMBINE=device \
    python -m pytest tests/test_multichip.py tests/test_shard_exec.py \
    tests/test_device_pipeline.py tests/test_search.py -q \
    -m 'not slow' -p no:cacheprovider -p no:xdist -p no:randomly
rc10=$?

# Pass 11 is the memory-accounting parity leg: serene_mem_account is
# forced ON globally (the conftest env hook arms the global) over the
# resources, profiler, parallel and shard parity suites — every
# statement then charges live/peak bytes at its materialization sites
# and registers live progress rows while the suites' parity matrices
# assert results stay bit-identical at any worker/shard count.
echo "== memory accounting parity pass (serene_mem_account=on) =="
timeout -k 10 600 env JAX_PLATFORMS=cpu SERENE_MEM_ACCOUNT=on \
    python -m pytest tests/test_resources.py tests/test_profile.py \
    tests/test_parallel_exec.py tests/test_shard_exec.py -q \
    -m 'not slow' -p no:cacheprovider -p no:xdist -p no:randomly
rc11=$?

# Pass 12 is the workload-governor parity leg: admission control is
# armed suite-wide (SERENE_MAX_CONCURRENT_STATEMENTS=8 — every
# non-exempt statement takes or queues for a governor slot) with a
# generous global SERENE_WORK_MEM ceiling (2GB — the budget check runs
# against every accounted statement without firing) and fair-share
# picking forced on, over the admission, parallel, shard and resources
# suites — proving the governor steers WHEN statements run, never what
# they return: a single diverged bit fails the parity assertions
# loudly.
echo "== workload governor parity pass (admission armed suite-wide) =="
timeout -k 10 600 env JAX_PLATFORMS=cpu \
    SERENE_MAX_CONCURRENT_STATEMENTS=8 SERENE_WORK_MEM=2GB \
    SERENE_FAIR_SHARE=on \
    python -m pytest tests/test_admission.py tests/test_parallel_exec.py \
    tests/test_shard_exec.py tests/test_resources.py -q \
    -m 'not slow' -p no:cacheprovider -p no:xdist -p no:randomly
rc12=$?

# Pass 13 is the device-telemetry parity leg: telemetry is forced ON
# with the compiled-program LRU capped at 4 entries (the conftest env
# hooks arm both globals) over the device-observability, device,
# multichip, shard and trace suites — the tiny cap exercises program
# eviction + re-compile on practically every suite query, proving the
# bounded compile ledger changes WHEN programs compile, never a result
# bit, while the telemetry ledgers record suite-wide.
echo "== device telemetry parity pass (telemetry on, program cache capped at 4) =="
timeout -k 10 600 env JAX_PLATFORMS=cpu SERENE_DEVICE_TELEMETRY=on \
    SERENE_PROGRAM_CACHE_ENTRIES=4 \
    python -m pytest tests/test_device_obs.py tests/test_device_pipeline.py \
    tests/test_device_agg.py tests/test_multichip.py \
    tests/test_shard_exec.py tests/test_trace.py -q \
    -m 'not slow' -p no:cacheprovider -p no:xdist -p no:randomly
rc13=$?

# Pass 14 is the fused-admission parity leg, two runs over the new
# admission/chaining suites: (a) the whole fused tier forced OFF
# globally — every widened shape (string/FILTER/DISTINCT aggregates,
# outer joins, residual predicates, chained agg→top-N) answers from
# the host oracle and the suites' differential assertions still
# exercise both paths via their explicit session SETs; (b) the tier ON
# with SERENE_DEVICE_FUSED_EXT=off — the PR-7 admission walls
# restored, proving the widening is strictly additive: old shapes
# still admit, new shapes decline cleanly to bit-identical host runs.
echo "== fused admission parity pass (fused off / ext off) =="
timeout -k 10 600 env JAX_PLATFORMS=cpu SERENE_DEVICE_FUSED=off \
    python -m pytest tests/test_fused_admission.py \
    tests/test_device_pipeline.py -q \
    -m 'not slow' -p no:cacheprovider -p no:xdist -p no:randomly
rc14=$?
if [ "$rc14" -eq 0 ]; then
    timeout -k 10 600 env JAX_PLATFORMS=cpu SERENE_DEVICE_FUSED_EXT=off \
        python -m pytest tests/test_fused_admission.py \
        tests/test_device_pipeline.py -q \
        -m 'not slow' -p no:cacheprovider -p no:xdist -p no:randomly
    rc14=$?
fi

# Pass 15 is the streaming-ingest parity leg: parallel analysis is
# forced ON with the segment-merge ladder pinned at a tiny cap of 3
# (the conftest env hooks arm serene_parallel_ingest and
# serene_max_segments) over the storage, segment, search, ES API and
# ingest-stream suites — every index build then chunk-splits across
# the worker pool and practically every append walks the tiered merge
# ladder, proving the parallel analysis merge and the background
# maintenance tiers are publish-mechanics only: a single diverged
# result bit fails the suites' parity assertions loudly.
echo "== streaming ingest parity pass (parallel ingest on, 3-segment cap) =="
timeout -k 10 600 env JAX_PLATFORMS=cpu SERENE_PARALLEL_INGEST=on \
    SERENE_INGEST_CHUNK_DOCS=64 SERENE_MAX_SEGMENTS=3 \
    python -m pytest tests/test_storage.py tests/test_segments.py \
    tests/test_search.py tests/test_es_api.py \
    tests/test_ingest_stream.py -q \
    -m 'not slow' -p no:cacheprovider -p no:xdist -p no:randomly
rc15=$?

# Pass 16 is the vector-retrieval leg, two runs over the vector/search
# serving suites: (a) the paged vector pool forced ON with the page
# budget starved at 16 pages — practically every knn/MaxSim dispatch
# then walks partial residency, cold-path fallback and LRU eviction,
# proving the pool changes WHERE vectors are scored (HBM region vs
# per-call upload), never a result bit; (b) serene_nprobe pinned at
# 4096 — every probe search degenerates to a full-cluster scan, so the
# suites' brute-force parity oracles must match bit-for-bit, proving
# the cluster-probe path IS the exact path restricted to a candidate
# set, not an approximation of it.
echo "== vector retrieval pass (pool starved at 16 pages / full probe) =="
timeout -k 10 600 env JAX_PLATFORMS=cpu SERENE_VECTOR_POOL=on \
    SERENE_VECTOR_PAGES=16 \
    python -m pytest tests/test_vector_store.py tests/test_vector.py \
    tests/test_search.py tests/test_es_api.py -q \
    -m 'not slow' -p no:cacheprovider -p no:xdist -p no:randomly
rc16=$?
if [ "$rc16" -eq 0 ]; then
    timeout -k 10 600 env JAX_PLATFORMS=cpu SERENE_NPROBE=4096 \
        python -m pytest tests/test_vector_store.py tests/test_vector.py \
        tests/test_es_api.py -q \
        -m 'not slow' -p no:cacheprovider -p no:xdist -p no:randomly
    rc16=$?
fi

echo "== front-door serving pass (socket admission forced at 8 connections) =="
# Pass 17, PR 20's asyncio front door: the pgwire/HTTP/ES suites plus the new
# transport suite all run with serene_max_connections=8 FORCED, so every
# keep-alive leak or unreleased gate slot in any suite turns into a hard
# 429/53300 failure within eight connections instead of surviving unseen
timeout -k 10 600 env JAX_PLATFORMS=cpu SERENE_MAX_CONNECTIONS=8 \
    python -m pytest tests/test_frontdoor.py tests/test_pgwire.py \
    tests/test_es_api.py tests/test_admission.py -q \
    -m 'not slow' -p no:cacheprovider -p no:xdist -p no:randomly
rc17=$?
if [ "$rc17" -eq 0 ]; then
    # parity leg: the same serving suites with the front door OFF (the
    # legacy thread-per-connection oracle kept for one release) — the
    # route tables are shared, so divergence here is a transport bug
    timeout -k 10 600 env JAX_PLATFORMS=cpu SERENE_FRONTDOOR=off \
        python -m pytest tests/test_pgwire.py tests/test_es_api.py -q \
        -m 'not slow' -p no:cacheprovider -p no:xdist -p no:randomly
    rc17=$?
fi

# Structural grep lint: every jit compilation in the engine must route
# through the PR 15 compile ledger (obs/device.compiled) so the program
# cache stays bounded and observable — a bare jax.jit( call site
# anywhere outside obs/device.py (or the ops/ kernel modules, which
# pre-date the ledger and are wrapped at their call sites) regresses
# the invariant.
echo "== compile-ledger grep lint =="
rc_lint=0
if grep -rn "jax\.jit(" serenedb_tpu/ \
        --include='*.py' \
        | grep -v "^serenedb_tpu/obs/device.py:" \
        | grep -v "^serenedb_tpu/ops/" \
        | grep -v "#.*jax\.jit("; then
    echo "FAIL: bare jax.jit( outside obs/device.py and ops/ kernels"
    rc_lint=1
fi
# PR 17's widened fused tier: the chained agg→top-N stage-2 builder is
# the newest program family — it must compile through the ledger,
# never via a bare jit
if ! grep -q '"fused_chain"' serenedb_tpu/exec/device_pipeline.py || \
        ! grep -q 'obs_device\.compiled(' \
            serenedb_tpu/exec/device_pipeline.py; then
    echo "FAIL: chained fused top-N does not compile through obs.device.compiled"
    rc_lint=1
fi
# PR 19's vector subsystem: unlike the older ops/ kernels, ops/vector.py
# post-dates the ledger — it gets NO bare-jit exemption, and both it and
# the paged vector store must compile every program family through the
# ledger so probe/rescore/MaxSim programs show up in the bounded cache.
if grep -n "jax\.jit(" serenedb_tpu/ops/vector.py \
        | grep -v "#.*jax\.jit("; then
    echo "FAIL: bare jax.jit( in ops/vector.py — vector kernels must use the ledger"
    rc_lint=1
fi
if ! grep -q 'obs_device\.compiled(' serenedb_tpu/ops/vector.py; then
    echo "FAIL: ops/vector.py does not compile through obs.device.compiled"
    rc_lint=1
fi
if ! grep -q 'obs_device\.compiled(' serenedb_tpu/search/vector_store.py; then
    echo "FAIL: vector_store.py does not compile through obs.device.compiled"
    rc_lint=1
fi

[ "$rc" -ne 0 ] && exit "$rc"
[ "$rc2" -ne 0 ] && exit "$rc2"
[ "$rc3" -ne 0 ] && exit "$rc3"
[ "$rc4" -ne 0 ] && exit "$rc4"
[ "$rc5" -ne 0 ] && exit "$rc5"
[ "$rc6" -ne 0 ] && exit "$rc6"
[ "$rc7" -ne 0 ] && exit "$rc7"
[ "$rc8" -ne 0 ] && exit "$rc8"
[ "$rc9" -ne 0 ] && exit "$rc9"
[ "$rc10" -ne 0 ] && exit "$rc10"
[ "$rc11" -ne 0 ] && exit "$rc11"
[ "$rc12" -ne 0 ] && exit "$rc12"
[ "$rc13" -ne 0 ] && exit "$rc13"
[ "$rc14" -ne 0 ] && exit "$rc14"
[ "$rc15" -ne 0 ] && exit "$rc15"
[ "$rc16" -ne 0 ] && exit "$rc16"
[ "$rc17" -ne 0 ] && exit "$rc17"
exit "$rc_lint"
