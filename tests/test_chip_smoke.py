"""chip_smoke.py contract tests that need no accelerator: without a TPU
the script fails fast and prints no result; its parent stays off jax;
its plain references agree with the repo's own host oracles on a small
input (so a chip run is held to a reference that is itself checked)."""

import importlib.util
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke_ut", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_no_tpu_exits_nonzero_fast_naming_platform(tmp_path):
    """JAX_PLATFORMS=cpu: non-zero, quickly, the platform named on
    stderr, and stdout EMPTY — the first stdout line is the `start`
    record, printed only after a TPU was seen and before any data is
    generated or loaded."""
    t0 = time.monotonic()
    r = subprocess.run(
        [sys.executable, SMOKE, "--workdir", str(tmp_path / "work")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
        capture_output=True, text=True, timeout=300)
    took = time.monotonic() - t0
    assert r.returncode != 0
    assert r.stdout == ""
    assert "platform 'cpu'" in r.stderr
    assert took < 120, f"took {took:.0f}s to notice there is no TPU"
    assert not (tmp_path / "work").exists()


def test_alone_in_a_directory_exits_nonzero_without_result(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       env={k: v for k, v in os.environ.items()
                            if k != "PYTHONPATH"},
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout == ""
    assert "serened exited" in r.stderr


def test_parent_module_stays_off_jax():
    r = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.argv = ['chip_smoke.py']\n"
         "import chip_smoke\n"
         "assert 'jax' not in sys.modules, 'import pulled jax in'\n"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]


def test_bm25_reference_agrees_with_host_oracle(smoke, tmp_path):
    """The smoke's float64 numpy BM25 over the GENERATED token arrays
    equals the repo's exhaustive host scorer over the index built from
    the generated TEXT (f32 vs f64: 1e-5) — generator, tokenization and
    formula all line up."""
    import pyarrow.parquet as pq

    from serenedb_tpu.search.analysis import get_analyzer
    from serenedb_tpu.search.searcher import SegmentSearcher
    from serenedb_tpu.search.segment import build_field_index
    path = str(tmp_path / "docs.parquet")
    docs = smoke.gen_docs(0, 4000, path)
    texts = pq.read_table(path).column("body").to_pylist()
    an = get_analyzer("simple")
    seg = SegmentSearcher(build_field_index(texts, an), an, len(texts))
    for terms, require_all in (([10], False), ([19, 208], False),
                               ([1, 2], True)):
        ref = smoke.bm25_reference(docs, terms, require_all)
        tids = [seg.index.term_id(f"w{t}") for t in terms]
        assert min(tids) >= 0
        match = np.flatnonzero(ref > 0).astype(np.int32)
        s, d = seg._cpu_score(match, tids, 10)
        got = list(zip(d.tolist(), s.tolist()))
        # held to a far tighter tolerance than the chip rule
        for doc, score in got:
            assert abs(score - ref[doc]) <= 1e-5 * abs(ref[doc])
        smoke.check_bm25("host oracle", got, ref, 10)


def test_bm25_rule_rejects_wrong_answers(smoke, tmp_path):
    docs = smoke.gen_docs(0, 3000, str(tmp_path / "d.parquet"))
    ref = smoke.bm25_reference(docs, [19, 208], False)
    order = np.lexsort((np.arange(len(ref)), -ref))[:10]
    good = [(int(d), float(ref[d])) for d in order]
    smoke.check_bm25("good", good, ref, 10)
    # a better document left out
    worse = int(np.lexsort((np.arange(len(ref)), -ref))[40])
    with pytest.raises(smoke.SmokeFailure):
        smoke.check_bm25("left out", good[:9] + [(worse, float(ref[worse]))],
                         ref, 10)
    # a score outside the tolerance
    bad = [(d, s * (1 + 10 * smoke.BM25_RTOL) + 10 * smoke.BM25_ATOL)
           for d, s in good[:1]] + good[1:]
    with pytest.raises(smoke.SmokeFailure):
        smoke.check_bm25("bad score", bad, ref, 10)
    # too few hits
    with pytest.raises(smoke.SmokeFailure):
        smoke.check_bm25("short", good[:5], ref, 10)


def test_knn_reference_is_exact_with_row_tiebreak(smoke, tmp_path):
    vec = smoke.gen_vectors(0, 2000, str(tmp_path / "v.parquet"))
    # grid data: f32 and f64 brute force agree to the bit
    d64 = ((vec["mat"].astype(np.float64) -
            vec["queries"][0].astype(np.float64)) ** 2).sum(axis=1)
    got = smoke.knn_reference(vec, 0, 50)
    assert [d for _, d in got] == sorted(d for _, d in got)
    for rid, d in got:
        assert d == d64[rid]
    for (r1, d1), (r2, d2) in zip(got, got[1:]):
        assert d1 < d2 or r1 < r2
