import os
import subprocess
import sys

import pytest

from serenedb_tpu.utils import config, faults, log, metrics, ticks


def test_settings_session_overrides_global():
    s = config.SessionSettings()
    assert s.get("sdb_nprobe") == 8
    s.set("sdb_nprobe", "16")
    assert s.get("sdb_nprobe") == 16
    s.reset("sdb_nprobe")
    assert s.get("sdb_nprobe") == 8
    with pytest.raises(KeyError):
        s.get("no_such_setting")


def test_settings_bool_coercion():
    s = config.SessionSettings()
    s.set("sdb_strict_ddl", "on")
    assert s.get("sdb_strict_ddl") is True
    s.set("sdb_strict_ddl", "off")
    assert s.get("sdb_strict_ddl") is False
    with pytest.raises(ValueError):
        s.set("sdb_strict_ddl", "maybe")


def test_fault_arming_spec():
    faults.arm_from_spec("a,b")
    assert faults.armed("a") and faults.armed("b")
    faults.arm_from_spec("-a")
    assert not faults.armed("a") and faults.armed("b")
    faults.arm_from_spec("+c")
    assert faults.armed("b") and faults.armed("c")
    faults.arm_from_spec("")
    assert not faults.armed("b")
    faults.arm_from_spec("x")
    with pytest.raises(faults.FaultInjected):
        faults.if_failure("x")
    faults.if_failure("unarmed")  # no-op


def test_gauge_scoped():
    g = metrics.REGISTRY.gauge("TestGauge")
    with g.scoped():
        assert g.value == 1
    assert g.value == 0


def test_log_ring():
    log.info("test", "hello")
    recs = log.MANAGER.records()
    assert any(r.message == "hello" and r.topic == "test" for r in recs)


def test_tick_bands():
    t = ticks.TickServer()
    first = t.next(5)
    assert first == 1
    assert t.current() == 5
    assert t.next() == 6
    t.advance_to(100)
    assert t.next() == 101


# -- entry-point contracts (no device dispatch): the compile cache is
# placed from outside when the environment says so; the native build is
# keyed on content

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_py(code, **env_changes):
    """python -c from the repo root; env_changes value None unsets."""
    env = {k: v for k, v in {**os.environ, **env_changes}.items()
           if v is not None}
    return subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=120)


# -- compile cache placement --------------------------------------------------


_CACHE_PROBE = (
    "import jax\n"
    "from serenedb_tpu.utils import backend\n"
    "d = backend.configure_compile_cache()\n"
    "print(d)\n"
    "print(jax.config.jax_compilation_cache_dir)\n")


def test_compile_cache_left_alone_when_env_sets_it(tmp_path):
    want = str(tmp_path / "outside_cache")
    r = _run_py(_CACHE_PROBE, JAX_COMPILATION_CACHE_DIR=want)
    assert r.returncode == 0, r.stderr[-2000:]
    helper_dir, jax_dir = r.stdout.strip().splitlines()[-2:]
    assert helper_dir == want
    assert jax_dir == want           # jax's own read of the variable
    assert not os.path.exists(want)  # the helper created nothing there


def test_compile_cache_defaults_to_checkout():
    r = _run_py(_CACHE_PROBE, JAX_COMPILATION_CACHE_DIR=None)
    assert r.returncode == 0, r.stderr[-2000:]
    helper_dir, jax_dir = r.stdout.strip().splitlines()[-2:]
    assert helper_dir == jax_dir == os.path.join(REPO, ".jax_cache")


def test_cache_keys_name_sources_from_the_checkout_root(tmp_path):
    """A Pallas kernel's serialized MLIR names its call stack's files and
    is part of the cache key: the path up to the checkout must not be."""
    r = _run_py(
        "import jax, jax.numpy as jnp\n"
        "from serenedb_tpu.utils import backend\n"
        "from serenedb_tpu.ops import agg\n"
        "backend.configure_compile_cache()\n"
        "x = jnp.zeros((8, 128), jnp.int32)\n"
        "print(jax.jit(lambda c: agg.group_count_scatter(c, c > 0, 4))"
        ".lower(x).as_text(debug_info=True))\n",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "c"), JAX_PLATFORMS="cpu")
    assert r.returncode == 0, r.stderr[-2000:]
    assert '"serenedb_tpu/ops/agg.py"' in r.stdout
    assert REPO not in r.stdout


def test_import_sets_no_cache_dir():
    """The helper runs only when an entry point calls it."""
    r = _run_py(
        "import jax, serenedb_tpu.engine, serenedb_tpu.utils.backend\n"
        "print(jax.config.jax_compilation_cache_dir)\n",
        JAX_COMPILATION_CACHE_DIR=None)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip().splitlines()[-1] == "None"


# -- native build keyed on content --------------------------------------------


def test_native_build_keyed_on_source_content():
    """The rebuild decision reads the source bytes and the flags, never
    mtimes: whatever the tree's mtimes, the loaded library is the one
    named by the content hash."""
    import hashlib

    from serenedb_tpu import native
    src = os.path.join(os.path.dirname(native.__file__), "indexer.cpp")
    with open(src, "rb") as f:
        body = f.read()
    want = hashlib.sha256(
        " ".join(native._CXX_FLAGS).encode() + b"\0" + body).hexdigest()[:16]
    assert native.load() is not None
    so = os.path.join(os.path.dirname(native.__file__), "_build",
                      f"libsdbnative-{want}.so")
    assert os.path.exists(so)
