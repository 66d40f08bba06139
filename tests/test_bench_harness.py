"""Entry-point contract tests (no device dispatch): the reduced bench.py
parent, the compile-cache helper, and the native build key.

The contracts: a parent that drives the chip through children never
imports jax; a failed shape, a device shape that ran anywhere but a TPU,
or a harness exception is a non-zero exit — never a substituted number;
the compile cache is placed from outside when the environment says so.
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_bench():
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def bench():
    return _load_bench()


def _stub_children(bench, monkeypatch, platform_of=None, fail=()):
    """Replace the child launcher: every shape 'runs' instantly on the
    platform its class requires unless told otherwise."""
    platform_of = platform_of or {}

    def run(name, timeout_s):
        if name in fail:
            return {}, "AssertionError: device/CPU result mismatch"
        plat = platform_of.get(name, bench._required_platform(name))
        return {"shape": name, "speedup": 2.0,
                "extra": {"platform": plat}}, ""

    monkeypatch.setattr(bench, "_run_shape_subprocess", run)


def _summary(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_all_shapes_on_required_platform_exit_zero(bench, capsys,
                                                   monkeypatch):
    _stub_children(bench, monkeypatch)
    assert bench.main([]) == 0
    out = _summary(capsys)
    assert "errors" not in out
    assert out["value"] == 2.0
    assert set(bench.SHAPES) == {k[:-len("_speedup")]
                                 for k in out["detail"]
                                 if k.endswith("_speedup")}


def test_failed_shape_is_nonzero_and_reported(bench, capsys, monkeypatch):
    _stub_children(bench, monkeypatch, fail=("hits",))
    assert bench.main(["q1", "hits"]) != 0
    out = _summary(capsys)
    assert "mismatch" in out["errors"]["hits"]
    assert "hits_speedup" not in out["detail"]
    assert out["detail"]["q1_speedup"] == 2.0


def test_device_shape_off_tpu_is_nonzero(bench, capsys, monkeypatch):
    """A device shape that ran on the CPU backend is an error, and its
    number never enters the detail under a device metric's name."""
    _stub_children(bench, monkeypatch, platform_of={"bm25": "cpu"})
    assert bench.main(["bm25", "host_agg"]) != 0
    out = _summary(capsys)
    assert "'cpu'" in out["errors"]["bm25"]
    assert "'tpu'" in out["errors"]["bm25"]
    assert "bm25_speedup" not in out["detail"]
    assert out["detail"]["host_agg_platform"] == "host"
    assert out["value"] == 0.0


def test_shape_classes_partition(bench):
    assert set(bench.HOST_SHAPES) <= set(bench.SHAPES)
    assert set(bench.VIRTUAL_MESH_SHAPES) <= set(bench.SHAPES)
    assert not set(bench.HOST_SHAPES) & set(bench.VIRTUAL_MESH_SHAPES)
    for name in bench.HEADLINE_SHAPES:
        assert bench._required_platform(name) == "tpu"
    assert bench._required_platform("multichip") == "cpu"
    assert bench._required_platform("ingest") == "host"


def test_unknown_shape_is_refused(bench, capsys, monkeypatch):
    _stub_children(bench, monkeypatch)
    assert bench.main(["nosuch"]) == 2
    assert _summary(capsys)["errors"]["unknown_shapes"] == ["nosuch"]


def test_harness_exception_propagates(bench, monkeypatch):
    """No catch-all that prints a zero and exits 0."""
    def boom(name, timeout_s):
        raise RuntimeError("harness bug")

    monkeypatch.setattr(bench, "_run_shape_subprocess", boom)
    with pytest.raises(RuntimeError):
        bench.main(["q1"])


def test_life_support_is_gone(bench):
    for name in ("_probe_device", "ledger_main", "_load_ledger",
                 "_save_ledger", "_acquire_bench_lock", "JIT_HOST_SHAPES",
                 "LEDGER_PATH", "_LOCK_PATH", "_STOP_PATH"):
        assert not hasattr(bench, name), name
    src = open(os.path.join(REPO, "bench.py")).read()
    assert "force_cpu" not in src and "SDB_BENCH_FORCE_CPU" not in src
    assert not os.path.exists(os.path.join(REPO, "scripts",
                                           "ledger_loop.sh"))


def _run_py(code, **env_changes):
    """python -c from the repo root; env_changes value None unsets."""
    env = {k: v for k, v in {**os.environ, **env_changes}.items()
           if v is not None}
    return subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=120)


def test_bench_parent_never_imports_jax():
    """Loading bench.py and driving main() over every shape (children
    stubbed) leaves jax unimported in the parent process."""
    code = (
        "import importlib.util, sys\n"
        "spec = importlib.util.spec_from_file_location('b', 'bench.py')\n"
        "b = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(b)\n"
        "b._run_shape_subprocess = lambda n, t: ({'speedup': 1.0, 'extra':"
        " {'platform': b._required_platform(n)}}, '')\n"
        "rc = b.main([])\n"
        "assert rc == 0, rc\n"
        "assert 'jax' not in sys.modules, 'parent imported jax'\n")
    r = _run_py(code)
    assert r.returncode == 0, r.stderr[-2000:]


def test_child_failure_exits_nonzero():
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py"),
                        "--shape", "nosuch"], capture_output=True,
                       text=True, timeout=120, cwd=REPO)
    assert r.returncode != 0
    assert "error" in json.loads(r.stdout.strip().splitlines()[-1])


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
