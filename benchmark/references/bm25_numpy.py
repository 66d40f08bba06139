"""Plain reference `bm25_numpy`: BM25 top-10 and the exact total, float64,
brute force. Imports nothing of `serenedb_tpu`; reads only the generator's
token arrays (`toks`, `doc_of`, `lens`).

BM25 as published (Lucene's): idf = ln(1 + (N - df + 0.5) / (df + 0.5)),
score = sum over the question's terms of idf * (k1 + 1) * tf /
(tf + k1 * (1 - b + b * dl / avgdl)), over EVERY posting of the terms
(no pruning), k1 and b from the configuration's `bm25`. The top-10 is by
(score descending, passage number ascending) — the program's stated tie
rule (`jax.lax.top_k`: lowest index first) — and the total is the exact
count of passages that hold any of the terms.

`check` compares, after the window has closed: every answered operation
for shape (at most `size` hits, exactly min(size, total) of them, ids in
range and distinct, scores non-increasing), and a sample of
`check["sample"]` of them, drawn with a seed the server never saw, in
full. It returns the numbers `correct` rests on:

  wrong_hits         hits that are not in order, out of range, repeated or
                     missing, plus, in the sample: an id outside the
                     reference's top-10 where no tie within the score
                     tolerance explains it, or an id that does not match
  wrong_totals       sampled answers whose total is not the exact count
                     (or not marked "eq")
  score_rel_err_max  largest |score - reference| / reference over the
                     sampled hits (f32 elementwise against float64)

The CONTROL is this reference computed in bfloat16 (contributions and
running sums rounded to 8 bits of mantissa), put in the program's place:
it must come out not correct.
"""

from __future__ import annotations

#: a hit outside the reference's top-10 is explained by a tie when its
#: reference score is within this of the reference's 10th (the limit on
#: `score_rel_err_max` is the same figure: f32 against float64)
TIE_RTOL = 1e-5


def posting_bytes(n_postings: float) -> float:
    """Bytes the narrowest lossless posting list of `n_postings` entries
    holds: an int32 passage number and one byte of term frequency each.
    What `score_roofline` divides by the HBM peak."""
    return 5.0 * n_postings


def _bf16(x):
    """float64 -> the nearest bfloat16, as float64 (round to nearest
    even on the upper 16 bits of the float32)."""
    import numpy as np
    u = np.asarray(x, dtype=np.float32).view(np.uint32).astype(np.uint64)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
    return u.astype(np.uint32).view(np.float32).astype(np.float64)


class Index:
    """The collection inverted once, by one stable sort of the token
    array: term t's postings are a slice of `docs` / `tfs`."""

    def __init__(self, dataset: dict, bm25: dict):
        import numpy as np
        toks, doc_of = dataset["toks"], dataset["doc_of"]
        self.n = int(dataset["n_docs"])
        self.dl = dataset["lens"].astype(np.float64)
        self.avgdl = float(self.dl.sum()) / self.n
        self.k1, self.b = float(bm25["k1"]), float(bm25["b"])
        # (term, passage) pairs in order; equal neighbours are one posting
        pair = toks.astype(np.int64) * self.n + doc_of
        pair.sort(kind="stable")
        first = np.ones(len(pair), dtype=bool)
        first[1:] = pair[1:] != pair[:-1]
        at = np.flatnonzero(first)
        self.tfs = np.diff(np.append(at, len(pair))).astype(np.float64)
        self.docs = (pair[at] % self.n).astype(np.int64)
        term = pair[at] // self.n
        n_terms = int(toks.max()) + 1 if len(toks) else 0
        self.start = np.searchsorted(term, np.arange(n_terms + 1))

    def df(self, t: int) -> int:
        return int(self.start[t + 1] - self.start[t])

    def score(self, terms, low_precision: bool = False):
        """(scores over every passage, matched mask) of one question."""
        import numpy as np
        rnd = _bf16 if low_precision else (lambda x: x)
        scores = np.zeros(self.n, dtype=np.float64)
        matched = np.zeros(self.n, dtype=bool)
        for t in dict.fromkeys(int(t) for t in terms):
            s, e = int(self.start[t]), int(self.start[t + 1])
            if e <= s:
                continue
            d, tf = self.docs[s:e], self.tfs[s:e]
            df = float(e - s)
            idf = np.log(1.0 + (self.n - df + 0.5) / (df + 0.5))
            denom = tf + self.k1 * (1.0 - self.b +
                                    self.b * self.dl[d] / self.avgdl)
            c = rnd(rnd(idf) * rnd((self.k1 + 1.0) * tf / denom))
            scores[d] = rnd(scores[d] + c)
            matched[d] = True
        return scores, matched

    def topk(self, scores, matched, k: int):
        """(passage numbers, scores) of the k best matching passages by
        (score descending, passage number ascending)."""
        import numpy as np
        cand = np.flatnonzero(matched)
        if len(cand) > k:
            s = scores[cand]
            kth = np.partition(s, len(s) - k)[len(s) - k]
            cand = cand[s >= kth]
        order = np.lexsort((cand, -scores[cand]))[:k]
        return cand[order], scores[cand[order]]


def shape_faults(answer: dict, n_docs: int, size: int) -> int:
    """Hits of one answer that break its shape."""
    hits = answer["hits"]
    total = answer["total"]
    if not isinstance(total, int) or total < 0:
        return max(len(hits), 1)
    bad = abs(len(hits) - min(size, total))
    seen = set()
    prev = float("inf")
    for hid, score in hits:
        ok = isinstance(hid, str) and hid.isdigit() and \
            int(hid) < n_docs and hid not in seen and \
            isinstance(score, (int, float)) and 0.0 < score <= prev
        seen.add(hid)
        if ok:
            prev = score
        else:
            bad += 1
    return bad


def compare(answer: dict, terms, index: Index, size: int,
            low_precision: bool = False) -> tuple[int, int, float]:
    """(wrong hits, wrong totals, largest relative score error) of one
    answer against the reference. With `low_precision` the ANSWER is
    replaced by the control's: the reference's own top-k computed in
    bfloat16."""
    ref, matched = index.score(terms)
    total = int(matched.sum())
    if low_precision:
        c_scores, c_matched = index.score(terms, True)
        ids, sc = index.topk(c_scores, c_matched, size)
        answer = {"total": int(c_matched.sum()), "relation": "eq",
                  "hits": [(str(int(i)), float(s)) for i, s in zip(ids, sc)]}
    top_ids, top_sc = index.topk(ref, matched, size)
    kth = float(top_sc[-1]) if len(top_sc) else 0.0
    inside = set(int(i) for i in top_ids)
    wrong, worst = 0, 0.0
    for hid, score in answer["hits"]:
        d = int(hid) if str(hid).isdigit() else -1
        if not (0 <= d < index.n) or not matched[d]:
            wrong += 1
            continue
        r = float(ref[d])
        worst = max(worst, abs(float(score) - r) / r)
        if d not in inside and r < kth * (1.0 - TIE_RTOL):
            wrong += 1
    wrong += abs(len(answer["hits"]) - min(size, total))
    bad_total = int(answer["total"] != total or
                    answer.get("relation") != "eq")
    return wrong, bad_total, worst


def check(ops, source, dataset, seed, check_spec, control=False, cfg=None):
    """({name: value}, answers compared in full). `ops` are the window's
    operations in the order they were sent; the k-th of a client is the
    k-th question the source gave that client."""
    import numpy as np
    size = source.size
    nth = [0] * len(source.sent)
    answered = []
    wrong_hits = 0
    for o in ops:
        k = nth[o["client"]]
        nth[o["client"]] += 1
        if not o["ok"]:
            continue
        answered.append((o["answer"], source.sent[o["client"]][k]))
        if not control:
            wrong_hits += shape_faults(o["answer"], int(dataset["n_docs"]),
                                       size)
    rng = np.random.default_rng([int(seed), 977])
    n = min(int(check_spec["sample"]), len(answered))
    pick = rng.choice(len(answered), n, replace=False) if n else []
    index = Index(dataset, cfg["bm25"])
    wrong_totals, worst = 0, 0.0
    for i in pick:
        w, t, e = compare(answered[int(i)][0], answered[int(i)][1], index,
                          size, low_precision=control)
        wrong_hits += w
        wrong_totals += t
        worst = max(worst, e)
    return ({"wrong_hits": wrong_hits, "wrong_totals": wrong_totals,
             "score_rel_err_max": worst}, n)
