"""Dataset `wiki`: the English Wikipedia of Search-Benchmark-the-Game
(`wiki-articles.json`, one JSON document an article, ONE text field),
made, not fetched.

There is no network here, so the corpus is built from what is known of
the source (`SOURCE` below, every figure written from memory and listed
under the configuration's `assumed`): articles of pseudo-words whose
lengths are an encyclopedia's — five times a passage's on average, with
a tail a hundred times it — whose term frequencies and vocabulary
growth follow `datasets/msmarco.py`'s rules (Zipf terms, Heaps' law at
the cut's own token count), and whose text holds COLLOCATIONS: a seeded
lexicon of two- to four-word sequences ("bank of america") written over
the independently drawn tokens at Zipf frequencies. Without them a
phrase of independently drawn tokens matches the document it was taken
from and nothing else, and a phrase query measures nothing.

`generate(cfg, seed, workdir)` writes one parquet file (`_id`, `_source`,
`body`) and returns the load statements plus the token arrays the plain
reference reads (`toks`, `doc_of`, `lens`, `bounds`) and what the query
source reads (`term_prob`, `words`, and where the collocations stand:
`colloc_at`, `colloc_len`). The same seed gives the same corpus.
"""

from __future__ import annotations

import os
import signal
import sys
import threading
import time

from .msmarco import term_probabilities, words

SOURCE = {
    # wiki-articles.json of search-benchmark-game (remembered)
    "docs": 5_032_105,
    # whitespace tokens an article: log-normal, mean about 280, median
    # about 170 (sigma 1.0), clipped to 20-8,000
    "len_mean": 280.0, "len_sigma": 1.0, "len_min": 20, "len_max": 8000,
    # Heaps' law V = K * N**BETA and the function words' share, as
    # datasets/msmarco.py draws them for English web text
    "heaps_k": 44.0, "heaps_beta": 0.55,
    "function_words": 100, "function_share": 0.45,
    # the lexicon of collocations: how many, how long, how much of the
    # text they hold, and how their frequencies fall (Zipf-Mandelbrot
    # (rank + q) ** -s: the offset keeps the most frequent collocation
    # in a tenth of the articles, not in most of them)
    "collocations": 200_000,
    "colloc_lengths": {2: 0.6, 3: 0.3, 4: 0.1},
    "colloc_token_share": 0.15,
    "colloc_zipf_s": 1.0, "colloc_zipf_q": 20.0,
}


#: the share of the machine's memory (MemTotal) past which a server child
#: is ended (`end_server_before_the_machine_does`): 31.5 of the one-chip
#: machine's 45 GiB, where a server that loads this corpus peaks at 24.6
#: and the machine ends the whole command at 40 (my chip runs, PR 33)
SERVER_MEMORY_SHARE = 0.7


def end_server_before_the_machine_does() -> None:
    """Watch the server child this process has started, and end it where
    its resident memory passes `SERVER_MEMORY_SHARE` of the machine's, so
    that the run FAILS CLEANLY (the load's connection drops: exit 1, no
    result line) where the machine would otherwise end the whole command
    (exit 137). This corpus can do that to a server: its longest article
    has 50,000 characters, and one that sorts a string column's
    dictionary through numpy's fixed-width unicode takes 4 B x the longest
    value for EVERY row, 62 GB for these 300,000, inside COPY. The commit
    before PR 33 does, and the check of a PR tries a new cell on the
    parent first, where a killed run refuses the PR and a clean failure
    does not (PERF.md section 6, PR 33). A memory limit is the harness's
    to set: a `benchmark` issue may move this there (PERF.md section 7)."""
    me = os.getpid()
    page = os.sysconf("SC_PAGE_SIZE")
    limit = SERVER_MEMORY_SHARE * os.sysconf("SC_PHYS_PAGES") * page
    pids = []       # `run.py` launches the server before it generates
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/stat") as f:
                if int(f.read().rsplit(")", 1)[1].split()[1]) == me:
                    pids.append(int(pid))
        except (OSError, ValueError, IndexError):
            continue

    def watch():
        while pids:
            time.sleep(0.2)
            for pid in list(pids):
                try:
                    with open(f"/proc/{pid}/statm") as f:
                        rss = int(f.read().split()[1]) * page
                except (OSError, ValueError, IndexError):
                    pids.remove(pid)        # it has exited
                    continue
                if rss > limit:
                    print(f"[bench] the server child {pid} holds "
                          f"{rss / (1 << 30):.1f} GiB, over "
                          f"{SERVER_MEMORY_SHARE:g} of the machine's "
                          "memory: ended, so that the run fails cleanly",
                          file=sys.stderr, flush=True)
                    os.kill(pid, signal.SIGKILL)
                    pids.remove(pid)

    threading.Thread(target=watch, name="bench-memory-guard",
                     daemon=True).start()


def collocation_lexicon(rng, prob, n: int):
    """(words (n, 4) int32 padded with -1, lengths (n,)): first and last
    word drawn by frequency from the terms below the function words,
    inner words from every term, so that "bank of america" can be."""
    import numpy as np
    top = SOURCE["function_words"]
    lens = rng.choice([int(k) for k in SOURCE["colloc_lengths"]], n,
                      p=list(SOURCE["colloc_lengths"].values()))
    cdf_all = np.cumsum(prob)
    cdf_all[-1] = 1.0
    content = prob[top:] / prob[top:].sum()
    cdf_content = np.cumsum(content)
    cdf_content[-1] = 1.0
    lex = np.full((n, 4), -1, dtype=np.int32)
    for j in range(4):
        inner = np.searchsorted(cdf_all, rng.random(n), side="right")
        edge = top + np.searchsorted(cdf_content, rng.random(n),
                                     side="right")
        is_edge = (j == 0) | (j == lens - 1)
        col = np.where(is_edge, edge, inner)
        lex[:, j] = np.where(j < lens, np.minimum(col, len(prob) - 1), -1)
    return lex, lens.astype(np.int32)


def write_collocations(rng, toks, bounds, doc_of, lex, lex_len):
    """Write collocation occurrences over `toks` in place; returns where
    they stand (sorted starts, lengths). An occurrence lies inside one
    document and overlaps no other."""
    import numpy as np
    n_tok = len(toks)
    ranks = np.arange(len(lex), dtype=np.float64)
    p = (ranks + 1.0 + SOURCE["colloc_zipf_q"]) ** -SOURCE["colloc_zipf_s"]
    cdf = np.cumsum(p / p.sum())
    cdf[-1] = 1.0
    mean_len = float(lex_len.mean())
    # a fifth more than the share asks for: about that many are dropped
    # where two would overlap or one would cross a document's end
    n_occ = int(1.2 * SOURCE["colloc_token_share"] * n_tok / mean_len)
    at = np.sort(rng.integers(0, n_tok, n_occ, dtype=np.int64))
    which = np.searchsorted(cdf, rng.random(n_occ), side="right")
    which = np.minimum(which, len(lex) - 1)
    ln = lex_len[which].astype(np.int64)
    end = at + ln
    keep = end <= n_tok
    keep[:-1] &= end[:-1] <= at[1:]
    keep &= doc_of[at] == doc_of[np.minimum(end, n_tok) - 1]
    at, which, ln = at[keep], which[keep], ln[keep]
    for j in range(4):
        has = ln > j
        toks[at[has] + j] = lex[which[has], j]
    return at, ln.astype(np.int32)


def generate(cfg: dict, seed: int, workdir: str) -> dict:
    import numpy as np
    import pyarrow as pa
    import pyarrow.compute as pc
    import pyarrow.parquet as pq
    n_docs = int(cfg["docs"])
    end_server_before_the_machine_does()
    rng = np.random.default_rng([seed, 33])
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
    doc_of = np.repeat(np.arange(n_docs, dtype=np.int32), lens)
    lex, lex_len = collocation_lexicon(rng, prob, SOURCE["collocations"])
    colloc_at, colloc_len = write_collocations(rng, toks, bounds, doc_of,
                                               lex, lex_len)
    vocab_words = words(vocab)
    varr = pa.array(vocab_words, pa.large_string())
    lists = pa.LargeListArray.from_arrays(pa.array(bounds),
                                          varr.take(pa.array(toks)))
    body = pc.binary_join(lists, pa.scalar(" ", pa.large_string()))
    ids = pa.array(np.arange(n_docs).astype(str), pa.string())
    src = pc.binary_join_element_wise(
        pa.scalar('{"id": '), ids, pa.scalar("}"), pa.scalar(""))
    path = os.path.join(workdir, "articles.parquet")
    pq.write_table(pa.table({"_id": ids, "_source": src,
                             "body": body.cast(pa.large_string())}),
                   path, compression="snappy")
    return {
        "load": [
            'CREATE TABLE wiki ("_id" VARCHAR, "_source" VARCHAR, '
            "body VARCHAR)",
            f"COPY wiki FROM '{path}' (FORMAT parquet)",
            "CREATE INDEX wiki_body ON wiki USING inverted (body) "
            "WITH (tokenizer = 'simple')"],
        "count": ("SELECT count(*) FROM wiki", n_docs),
        "params": {"index": "wiki", "field": "body"},
        "n_docs": n_docs, "toks": toks, "lens": lens, "bounds": bounds,
        "doc_of": doc_of, "term_prob": prob, "words": vocab_words,
        "colloc_at": colloc_at, "colloc_len": colloc_len,
        "colloc_token_share": float(colloc_len.sum()) / n_tok}
