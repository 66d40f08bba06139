"""Dataset `msmarco`: MS MARCO passage ranking, made, not fetched.

There is no network here, so the collection is built from what is known
of the source's statistics (`SOURCE` below, every figure written from
memory and listed under the configuration's `assumed`): passages of
pseudo-words whose lengths, term frequencies and vocabulary growth are
the source's, and questions built the way MS MARCO's are: answerable
from one passage, behind a frame of function words.

`generate(cfg, seed, workdir)` writes one parquet file (`_id`, `_source`,
`body`) and returns the load statements plus the token arrays the plain
reference reads (`toks`, `doc_of`, `lens`) and what the question source
reads (`term_prob`, `words`). The same seed gives the same collection and
the same questions.
"""

from __future__ import annotations

import os

SOURCE = {
    # collection.tsv of the MS MARCO passage ranking task
    "passages": 8_841_823,
    # queries.dev.small.tsv, of the 1,010,916 questions of the Bing log
    "queries_dev_small": 6_980,
    "queries_all": 1_010_916,
    # whitespace tokens per passage: mean about 56, between 10 and 250
    "len_mean": 56.0, "len_min": 10, "len_max": 250, "len_sigma": 0.45,
    # Heaps' law V = K * N**BETA through the collection's own point:
    # about 2.66M index terms over about 495M tokens
    "heaps_k": 44.0, "heaps_beta": 0.55,
    # the 100 most frequent terms (the function words) cover about 45%
    # of all tokens
    "function_words": 100, "function_share": 0.45,
    # questions: 2-15 terms, mean about 6, about 40% of their tokens
    # among the collection's 100 most frequent terms; 2-5 content terms
    "q_content": {2: 0.2, 3: 0.3, 4: 0.3, 5: 0.2},
    "q_function_mean": 2.4, "q_function_max": 10,
}

_CONS = "bcdfghjklmnprstvwz"
_VOW = "aeiou"


def words(n: int):
    """n distinct lower-case pseudo-words of letters only (any analyzer
    that lower-cases and splits on non-letters leaves them whole): two
    consonant-vowel syllables for the most frequent ranks, three and four
    further down."""
    import numpy as np
    syl = np.array([c + v for c in _CONS for v in _VOW])
    base = len(syl)
    out = np.empty(n, dtype=object)
    r = np.arange(n, dtype=np.int64)
    lo, width = 0, 2
    while lo < n:
        hi = min(n, lo + base ** width)
        idx = r[lo:hi] - lo
        parts = []
        for _ in range(width):
            parts.append(syl[idx % base])
            idx = idx // base
        w = parts[0]
        for p in parts[1:]:
            w = np.char.add(w, p)
        out[lo:hi] = w
        lo, width = hi, width + 1
    return out


def term_probabilities(vocab: int, top: int, share: float):
    """Zipf probabilities over `vocab` ranks, the exponent solved so that
    the `top` most frequent ranks hold `share` of the mass."""
    import numpy as np
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    lo, hi = 0.5, 2.0
    for _ in range(60):
        s = (lo + hi) / 2
        p = ranks ** -s
        if p[:top].sum() / p.sum() < share:
            lo = s
        else:
            hi = s
    p = ranks ** -((lo + hi) / 2)
    return p / p.sum()


def generate(cfg: dict, seed: int, workdir: str) -> dict:
    import numpy as np
    import pyarrow as pa
    import pyarrow.compute as pc
    import pyarrow.parquet as pq
    n_docs = int(cfg["passages"])
    rng = np.random.default_rng([seed, 26])
    mu = np.log(SOURCE["len_mean"]) - SOURCE["len_sigma"] ** 2 / 2
    lens = np.clip(np.rint(rng.lognormal(mu, SOURCE["len_sigma"], n_docs)),
                   SOURCE["len_min"], SOURCE["len_max"]).astype(np.int32)
    n_tok = int(lens.sum())
    vocab = int(SOURCE["heaps_k"] * n_tok ** SOURCE["heaps_beta"])
    prob = term_probabilities(vocab, SOURCE["function_words"],
                              SOURCE["function_share"])
    cdf = np.cumsum(prob)
    cdf[-1] = 1.0
    toks = np.empty(n_tok, dtype=np.int32)
    step = 1 << 24                       # in bulk, a slice at a time
    for at in range(0, n_tok, step):
        u = rng.random(min(step, n_tok - at))
        toks[at:at + step] = np.searchsorted(cdf, u, side="right")
    np.minimum(toks, vocab - 1, out=toks)
    bounds = np.concatenate([[0], np.cumsum(lens, dtype=np.int64)])
    vocab_words = words(vocab)
    varr = pa.array(vocab_words, pa.large_string())
    lists = pa.LargeListArray.from_arrays(pa.array(bounds),
                                          varr.take(pa.array(toks)))
    body = pc.binary_join(lists, pa.scalar(" ", pa.large_string()))
    ids = pa.array(np.arange(n_docs).astype(str), pa.string())
    src = pc.binary_join_element_wise(
        pa.scalar('{"pid": '), ids, pa.scalar("}"), pa.scalar(""))
    path = os.path.join(workdir, "passages.parquet")
    pq.write_table(pa.table({"_id": ids, "_source": src,
                             "body": body.cast(pa.large_string())}),
                   path, compression="snappy")
    return {
        "load": [
            'CREATE TABLE passages ("_id" VARCHAR, "_source" VARCHAR, '
            "body VARCHAR)",
            f"COPY passages FROM '{path}' (FORMAT parquet)",
            "CREATE INDEX passages_body ON passages USING inverted (body) "
            "WITH (tokenizer = 'simple')"],
        "count": ("SELECT count(*) FROM passages", n_docs),
        "params": {"index": "passages", "field": "body"},
        "n_docs": n_docs, "toks": toks, "lens": lens, "bounds": bounds,
        "doc_of": np.repeat(np.arange(n_docs, dtype=np.int32), lens),
        "term_prob": prob, "words": vocab_words}
