"""Test harness config: tests run on a virtual 8-device CPU mesh.

Tier-1 says whether answers are right, never how fast: it runs on
`JAX_PLATFORMS=cpu`, with multi-device sharding validated on virtual CPU
devices (jax's xla_force_host_platform_device_count), matching how
`__graft_entry__.dryrun_multichip` dry-runs. The chip is reached only
through `python chip_smoke.py`.

Must run before anything imports jax, hence top-of-conftest env mutation.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

assert len(jax.devices()) == 8, "virtual CPU mesh not active"

# this process has deliberately initialized its backend: let the passive
# device-count probe (parallel/mesh.device_count_if_initialized) see it
from serenedb_tpu.parallel import mesh as _sdb_mesh  # noqa: E402

_sdb_mesh.note_backend_initialized()

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# scripts/verify_tier1.sh arms the zone-map debug assert for one extra
# parity pass: every pruned morsel is re-scanned and any block-stats/data
# divergence fails the query loudly instead of sampling its way past.
if os.environ.get("SERENE_ZONEMAP_VERIFY"):
    from serenedb_tpu.utils.config import REGISTRY as _SDB_REGISTRY

    _SDB_REGISTRY.set_global("serene_zonemap_verify", True)

# scripts/verify_tier1.sh join-filter parity leg: force the sideways
# min/max join filter to the given value ("on"/"off") for a whole run —
# the off pass proves the filter is an optimization layer only (results
# identical without it), the on pass combines with SERENE_ZONEMAP_VERIFY
# so every join-filter-pruned probe morsel is re-scanned structurally.
if os.environ.get("SERENE_JOIN_FILTER"):
    from serenedb_tpu.utils.config import REGISTRY as _SDB_REG_JF

    _SDB_REG_JF.set_global("serene_join_filter",
                           os.environ["SERENE_JOIN_FILTER"])

# scripts/verify_tier1.sh profiler parity leg: force serene_profile to
# the given value ("on"/"off") for a whole run — the on pass proves the
# span instrumentation observes without changing a single result bit,
# the off pass that the engine runs clean with the collector absent.
if os.environ.get("SERENE_PROFILE"):
    from serenedb_tpu.utils.config import REGISTRY as _SDB_REG_PROF

    _SDB_REG_PROF.set_global("serene_profile",
                             os.environ["SERENE_PROFILE"])


# scripts/verify_tier1.sh result-cache parity leg: force
# serene_result_cache to the given value ("on"/"off") for a whole run —
# the on pass proves cached statements are bit-identical to executed
# ones across the parity suites, the off pass that the engine runs
# clean with both cache tiers absent.
if os.environ.get("SERENE_RESULT_CACHE"):
    from serenedb_tpu.utils.config import REGISTRY as _SDB_REG_RC

    _SDB_REG_RC.set_global("serene_result_cache",
                           os.environ["SERENE_RESULT_CACHE"])


# scripts/verify_tier1.sh fused-pipeline parity leg: force
# serene_device_fused to the given value ("on"/"off") for a whole run —
# the off pass proves the fused device tier is an optimization layer
# only (every suite passes with it globally dark), the on pass that the
# one-dispatch programs are bit-identical to the host oracle.
if os.environ.get("SERENE_DEVICE_FUSED"):
    from serenedb_tpu.utils.config import REGISTRY as _SDB_REG_DF

    _SDB_REG_DF.set_global("serene_device_fused",
                           os.environ["SERENE_DEVICE_FUSED"])


# scripts/verify_tier1.sh fused-admission parity leg: force
# serene_device_fused_ext to the given value ("on"/"off") for a whole
# run — the off pass restores the PR-7 admission walls (string/FILTER/
# DISTINCT aggregates, outer joins, residual predicates and the
# chained agg→top-N all fall back to the host oracle), proving the
# widened tier is an optimization layer only.
if os.environ.get("SERENE_DEVICE_FUSED_EXT"):
    from serenedb_tpu.utils.config import REGISTRY as _SDB_REG_DFX

    _SDB_REG_DFX.set_global("serene_device_fused_ext",
                            os.environ["SERENE_DEVICE_FUSED_EXT"])


# scripts/verify_tier1.sh search-batch parity leg: force
# serene_search_batch to the given value ("on"/"off") for a whole run —
# the off pass proves the query batcher is a dispatch-coalescing layer
# only (the search and ES suites are bit-identical with every query
# dispatched serially), the on pass that coalesced scoring perturbs
# nothing.
if os.environ.get("SERENE_SEARCH_BATCH"):
    from serenedb_tpu.utils.config import REGISTRY as _SDB_REG_SB

    _SDB_REG_SB.set_global("serene_search_batch",
                           os.environ["SERENE_SEARCH_BATCH"])


# scripts/verify_tier1.sh sharded-execution parity leg: force
# serene_shards to the given count (e.g. "4") for a whole run — the
# parallel/join/device/search parity suites then execute everything
# through the sharded tier, proving per-shard pipelines plus the
# cross-shard combiners are bit-identical to unsharded execution.
if os.environ.get("SERENE_SHARDS"):
    from serenedb_tpu.utils.config import REGISTRY as _SDB_REG_SH

    _SDB_REG_SH.set_global("serene_shards", os.environ["SERENE_SHARDS"])


# scripts/verify_tier1.sh multichip parity leg: force
# serene_shard_combine to the given value ("device"/"host"/"auto") for
# a whole run — combined with SERENE_SHARDS=4 the device pass executes
# every sharded fused pipeline as ONE shard_map collective dispatch and
# every sharded search merge as an in-program all_gather hop, proving
# the in-program combine is bit-identical to the host combine across
# the parity suites.
if os.environ.get("SERENE_SHARD_COMBINE"):
    from serenedb_tpu.utils.config import REGISTRY as _SDB_REG_SC

    _SDB_REG_SC.set_global("serene_shard_combine",
                           os.environ["SERENE_SHARD_COMBINE"])


# scripts/verify_tier1.sh timeline-tracing parity leg: force
# serene_trace to the given value ("on"/"off") for a whole run — the on
# pass proves span recording (pool queue waits, batcher fan-out, shard
# pipelines, device phases) observes without changing a single result
# bit, the off pass that the engine runs clean with the tracer absent.
if os.environ.get("SERENE_TRACE"):
    from serenedb_tpu.utils.config import REGISTRY as _SDB_REG_TR

    _SDB_REG_TR.set_global("serene_trace", os.environ["SERENE_TRACE"])


# scripts/verify_tier1.sh memory-accounting parity leg: force
# serene_mem_account to the given value ("on"/"off") for a whole run —
# the on pass proves per-query live/peak byte accounting + progress
# registration observe without changing a single result bit at any
# worker/shard count, the off pass that the engine runs clean with the
# accountant absent.
if os.environ.get("SERENE_MEM_ACCOUNT"):
    from serenedb_tpu.utils.config import REGISTRY as _SDB_REG_MA

    _SDB_REG_MA.set_global("serene_mem_account",
                           os.environ["SERENE_MEM_ACCOUNT"])


# scripts/verify_tier1.sh device-telemetry parity leg: force
# serene_device_telemetry to the given value ("on"/"off") and/or cap
# the compiled-program LRU at a tiny SERENE_PROGRAM_CACHE_ENTRIES
# (e.g. "4") for a whole run — the capped pass exercises program
# eviction + re-compile on every suite query, proving the bounded
# ledger changes WHEN programs compile, never what they compute.
if os.environ.get("SERENE_DEVICE_TELEMETRY"):
    from serenedb_tpu.utils.config import REGISTRY as _SDB_REG_DT

    _SDB_REG_DT.set_global("serene_device_telemetry",
                           os.environ["SERENE_DEVICE_TELEMETRY"])

if os.environ.get("SERENE_PROGRAM_CACHE_ENTRIES"):
    from serenedb_tpu.utils.config import REGISTRY as _SDB_REG_PC

    _SDB_REG_PC.set_global("serene_program_cache_entries",
                           os.environ["SERENE_PROGRAM_CACHE_ENTRIES"])


# scripts/verify_tier1.sh workload-governor parity leg: arm the
# admission gate suite-wide (e.g. "8" — every non-exempt statement then
# takes/queues for a governor slot), a generous global serene_work_mem
# ceiling (e.g. "2GB" — the budget check runs against every accounted
# statement without ever firing) and/or fair-share picking, proving the
# governor steers scheduling only: the admission/parallel/shard/
# resources suites must stay bit-identical with it armed.
# scripts/verify_tier1.sh pass 19 (front door): run the serving suites
# with the socket accept gate forced tiny (SERENE_MAX_CONNECTIONS=8 —
# the rejection path exercised suite-wide), or the asyncio tier swapped
# for the legacy ThreadingHTTPServer parity oracle
# (SERENE_FRONTDOOR=off), or idle reaping pinned on
_FRONTDOOR_ENV_HOOKS = {
    "SERENE_FRONTDOOR": "serene_frontdoor",
    "SERENE_MAX_CONNECTIONS": "serene_max_connections",
    "SERENE_IDLE_CONN_TIMEOUT_S": "serene_idle_conn_timeout_s",
}
for _env, _setting in _FRONTDOOR_ENV_HOOKS.items():
    if os.environ.get(_env):
        from serenedb_tpu.utils.config import REGISTRY as _SDB_REG_FD

        _SDB_REG_FD.set_global(_setting, os.environ[_env])


_GOVERNOR_ENV_HOOKS = {
    "SERENE_MAX_CONCURRENT_STATEMENTS": "serene_max_concurrent_statements",
    "SERENE_WORK_MEM": "serene_work_mem",
    "SERENE_FAIR_SHARE": "serene_fair_share",
}
for _env, _setting in _GOVERNOR_ENV_HOOKS.items():
    if os.environ.get(_env):
        from serenedb_tpu.utils.config import REGISTRY as _SDB_REG_GOV

        _SDB_REG_GOV.set_global(_setting, os.environ[_env])


# scripts/verify_tier1.sh streaming-ingest parity leg: force the
# write-path knobs to the given values for a whole run —
# SERENE_PARALLEL_INGEST=on (with a small SERENE_INGEST_CHUNK_DOCS so
# modest suite corpora actually chunk-split) proves the parallel
# analysis merge is bit-identical to the serial oracle suite-wide; a
# tiny SERENE_MAX_SEGMENTS walks the tiered merge ladder on practically
# every append; SERENE_BACKGROUND_MERGE/SERENE_GROUP_COMMIT flip the
# maintenance placement and fsync coalescing without a result-bit
# anywhere.
_INGEST_ENV_HOOKS = {
    "SERENE_PARALLEL_INGEST": "serene_parallel_ingest",
    "SERENE_INGEST_CHUNK_DOCS": "serene_ingest_chunk_docs",
    "SERENE_MAX_SEGMENTS": "serene_max_segments",
    "SERENE_BACKGROUND_MERGE": "serene_background_merge",
    "SERENE_GROUP_COMMIT": "serene_group_commit",
}
for _env, _setting in _INGEST_ENV_HOOKS.items():
    if os.environ.get(_env):
        from serenedb_tpu.utils.config import REGISTRY as _SDB_REG_ING

        _SDB_REG_ING.set_global(_setting, os.environ[_env])


# scripts/verify_tier1.sh vector-retrieval leg: force the paged vector
# pool to the given value ("on"/"off") and/or starve its page budget at
# a tiny SERENE_VECTOR_PAGES (e.g. "16") for a whole run — the starved
# pass forces cold-path fallback and LRU eviction on practically every
# knn/MaxSim dispatch, proving the pool changes WHERE vectors are
# scored (resident HBM region vs per-call upload), never a result bit.
# SERENE_NPROBE pins the probe width suite-wide (e.g. "4096" = every
# probe search degenerates to a full-cluster scan, so the brute-force
# parity oracles must match bit-for-bit); SERENE_MAXSIM flips the
# MaxSim scorer between the device program and the f64 host oracle.
_VECTOR_ENV_HOOKS = {
    "SERENE_VECTOR_POOL": "serene_vector_pool",
    "SERENE_VECTOR_PAGES": "serene_vector_pages",
    "SERENE_NPROBE": "serene_nprobe",
    "SERENE_MAXSIM": "serene_maxsim",
}
for _env, _setting in _VECTOR_ENV_HOOKS.items():
    if os.environ.get(_env):
        from serenedb_tpu.utils.config import REGISTRY as _SDB_REG_VEC

        _SDB_REG_VEC.set_global(_setting, os.environ[_env])


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running throughput tests, excluded from "
        "the tier-1 `-m 'not slow'` runs")


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture(autouse=True)
def _clear_faults():
    yield
    from serenedb_tpu.utils import faults
    faults.clear()
