"""Plain reference `knn_numpy`: exact cosine top-10 over EVERY generated
vector, float64, brute force. Imports nothing of `serenedb_tpu`; reads
only the generator's array (`emb`, float32 (passages, dims)) and the
questions the source sent.

Cosine as published: cos(q, x) = q.x / (|q| |x|), every product and sum
in float64, over every passage, in row blocks so that a block's float64
copy and its (questions x block) products fit host memory; the top-10 is
by (score descending, passage number ascending) — the program's stated
tie rule. A hit's `_score` is Elasticsearch's for `similarity: cosine`:
(1 + cos) / 2; `hits.total` of a knn search is {value: k, relation: eq}.

`check` compares, after the window has closed: every answered operation
for shape (exactly min(size, k, passages) hits, ids in range and distinct,
scores non-increasing and in [0, 1]), and a sample of `check["sample"]`
of them, drawn with a seed the server never saw, in full. It returns the
numbers `correct` rests on:

  wrong_hits         hits out of order, out of range, repeated or
                     missing, plus, in the sample: an id outside the
                     reference's top-10 that no tie within the score
                     tolerance explains
  wrong_totals       sampled answers whose total is not k (or not "eq")
  score_rel_err_max  largest |score - reference| / reference over the
                     sampled hits (float32 products against float64)

The CONTROL is this reference with vectors and question rounded to
bfloat16 (8 bits of mantissa; what one default-precision MXU pass keeps
of a float32), put in the program's place: it must come out not correct.
"""

from __future__ import annotations

#: a hit outside the reference's top-10 is explained by a tie when its
#: reference score is within this of the reference's 10th; the
#: configuration's limit on `score_rel_err_max`, when given, is used
#: instead (the same figure: float32 against float64)
TIE_RTOL = 1e-5

#: rows of `emb` held in float64 at a time
BLOCK = 32768


def scan_bytes(rows: float, dims: int) -> float:
    """Bytes an exact scan of `rows` float32 vectors of `dims` has to
    read: what `knn_roofline` divides by the HBM peak."""
    return float(rows) * dims * 4.0


def _bf16(x):
    """float -> the nearest bfloat16, as float64 (round to nearest even
    on the upper 16 bits of the float32)."""
    import numpy as np
    u = np.ascontiguousarray(x, dtype=np.float32).view(np.uint32)
    u = (u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return u.view(np.float32).astype(np.float64)


def topk(emb, questions, k: int, low_precision: bool = False):
    """(passage numbers (Q, k), cosines (Q, k)) of the k nearest passages
    of every question by (cosine descending, passage number ascending),
    float64, one pass over `emb` in row blocks."""
    import numpy as np
    rnd = _bf16 if low_precision else (lambda a: np.asarray(a, np.float64))
    q = rnd(np.asarray(questions, dtype=np.float32))
    q = q / np.maximum(np.linalg.norm(q, axis=1, keepdims=True), 1e-300)
    n = len(emb)
    k = min(k, n)
    best_s = np.full((len(q), 0), 0.0)
    best_i = np.zeros((len(q), 0), dtype=np.int64)
    for at in range(0, n, BLOCK):
        x = rnd(emb[at:at + BLOCK])
        nx = np.maximum(np.linalg.norm(x, axis=1), 1e-300)
        s = (q @ x.T) / nx[None, :]
        kk = min(k, s.shape[1])
        part = np.argpartition(-s, kk - 1, axis=1)[:, :kk]
        cand_s = np.concatenate(
            [best_s, np.take_along_axis(s, part, axis=1)], axis=1)
        cand_i = np.concatenate([best_i, part + at], axis=1)
        # (score desc, passage asc): lexsort's last key is the primary
        order = np.lexsort((cand_i, -cand_s), axis=1)[:, :k]
        best_s = np.take_along_axis(cand_s, order, axis=1)
        best_i = np.take_along_axis(cand_i, order, axis=1)
    return best_i, best_s


def cosines(emb, question, ids):
    """float64 cosines of one question with the passages `ids`."""
    import numpy as np
    q = np.asarray(question, np.float64)
    x = np.asarray(emb[np.asarray(ids, dtype=np.int64)], np.float64)
    return (x @ q) / np.maximum(
        np.linalg.norm(x, axis=1) * np.linalg.norm(q), 1e-300)


def es_score(cos):
    """Elasticsearch's `_score` of a knn hit under `similarity: cosine`."""
    return (1.0 + cos) / 2.0


def shape_faults(answer: dict, n_docs: int, size: int, k: int) -> int:
    """Hits of one answer that break its shape."""
    hits = answer["hits"]
    bad = abs(len(hits) - min(size, k, n_docs))
    seen = set()
    prev = float("inf")
    for hid, score in hits:
        ok = isinstance(hid, str) and hid.isdigit() and \
            int(hid) < n_docs and hid not in seen and \
            isinstance(score, (int, float)) and \
            0.0 <= score <= min(prev, 1.0 + 1e-6)
        seen.add(hid)
        if ok:
            prev = score
        else:
            bad += 1
    return bad


def compare(answer: dict, question, top_ids, top_cos, emb, size: int,
            k: int, tie: float) -> tuple[int, int, float]:
    """(wrong hits, wrong totals, largest relative score error) of one
    answer against the reference's top-k of its question."""
    n = len(emb)
    want = min(size, k, n)
    kth = float(es_score(top_cos[want - 1])) if want else 0.0
    inside = set(int(i) for i in top_ids[:want])
    ids = [int(h) if str(h).isdigit() and int(h) < n else -1
           for h, _ in answer["hits"]]
    ok = [i for i in ids if i >= 0]
    ref = dict(zip(ok, es_score(cosines(emb, question, ok)))) if ok else {}
    wrong, worst = 0, 0.0
    for i, (_hid, score) in zip(ids, answer["hits"]):
        if i < 0:
            wrong += 1
            continue
        r = float(ref[i])
        worst = max(worst, abs(float(score) - r) / max(r, 1e-300))
        if i not in inside and r < kth * (1.0 - tie):
            wrong += 1
    wrong += abs(len(answer["hits"]) - want)
    bad_total = int(answer["total"] != min(k, n) or
                    answer.get("relation") != "eq")
    return wrong, bad_total, worst


def check(ops, source, dataset, seed, check_spec, control=False, cfg=None):
    """({name: value}, answers compared in full). `ops` are the window's
    operations in the order they were sent; the j-th of a client is the
    j-th question the source gave that client."""
    import numpy as np
    size, k = source.size, source.k
    emb = dataset["emb"]
    n_docs = int(dataset["n_docs"])
    tie = float((cfg or {}).get("limits", {}).get("score_rel_err_max",
                                                  TIE_RTOL))
    nth = [0] * len(source.sent)
    answered = []
    wrong_hits = 0
    for o in ops:
        j = nth[o["client"]]
        nth[o["client"]] += 1
        if not o["ok"]:
            continue
        answered.append((o["answer"], source.sent[o["client"]][j]))
        if not control:
            wrong_hits += shape_faults(o["answer"], n_docs, size, k)
    rng = np.random.default_rng([int(seed), 977])
    n = min(int(check_spec["sample"]), len(answered))
    pick = [int(i) for i in rng.choice(len(answered), n, replace=False)] \
        if n else []
    wrong_totals, worst = 0, 0.0
    if pick:
        qs = np.stack([answered[i][1] for i in pick])
        top_ids, top_cos = topk(emb, qs, k)
        if control:
            c_ids, c_cos = topk(emb, qs, k, low_precision=True)
        for row, i in enumerate(pick):
            answer = answered[i][0]
            if control:
                want = min(size, k, n_docs)
                answer = {"total": min(k, n_docs), "relation": "eq",
                          "hits": [(str(int(d)), float(es_score(c)))
                                   for d, c in zip(c_ids[row][:want],
                                                   c_cos[row][:want])]}
            w, t, e = compare(answer, qs[row], top_ids[row], top_cos[row],
                              emb, size, k, tie)
            wrong_hits += w
            wrong_totals += t
            worst = max(worst, e)
    return ({"wrong_hits": wrong_hits, "wrong_totals": wrong_totals,
             "score_rel_err_max": worst}, n)
