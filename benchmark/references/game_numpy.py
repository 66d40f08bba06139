"""Plain reference `game_numpy`: Search-Benchmark-the-Game's four query
shapes under its three commands, float64, brute force. Imports nothing
of `serenedb_tpu`; reads only the generator's token arrays (`toks`,
`doc_of`, `lens`, `bounds`) and builds on `bm25_numpy` (the inverted
collection, BM25 as published, the tie rule).

Per operation (token ids, shape, command):

  match set   term: the documents that hold the one token; union: any of
              the tokens; intersection: all of them; phrase: the tokens at
              CONSECUTIVE positions of one document, found in the token
              array itself (no positional index is built)
  total       the size of the match set, exactly
  top-10      the ten best documents OF THE MATCH SET by BM25 — the sum of
              the query's DISTINCT tokens' contributions (a token given
              twice scores once; a phrase scores as its tokens do: the
              configuration's stated departure from Lucene, which scores
              the phrase's own frequency) — by (score descending, document
              number ascending); a hit outside the reference's ten is
              explained by a tie when its reference score is within
              `TIE_RTOL` of the tenth

`check` holds EVERY answered operation to its command's shape (COUNT: no
hits and an exact total; TOP_10: at most ten hits in order and NO total;
TOP_10_COUNT: both) and compares a sample of `check["sample"]` of them,
drawn with a seed the server never saw, in full. It returns

  wrong_hits         hits out of order, out of range, repeated, too many,
                     missing, present under COUNT; in the sample: an id
                     outside the match set or outside the reference's ten
                     with no tie to explain it
  wrong_totals       a total that is missing where the command asks for
                     one, present where it does not, not marked "eq"; in
                     the sample: not the exact count
  score_rel_err_max  largest |score - reference| / reference over the
                     sampled hits (f32 elementwise against float64)

Two CONTROLS, each the reference put in the program's place with part of
the work done worse; each must come out not correct:

  "bf16"          the top-10 scored in bfloat16 (bm25_numpy's control):
                  fails `score_rel_err_max`
  "no_adjacency"  a phrase read as an intersection (adjacency left out):
                  fails `wrong_totals`

`control=True` (what `run.py --control 1` passes) applies both at once;
each fails by a number the other cannot move (rounding moves no total,
and a document's score does not depend on adjacency).
"""

from __future__ import annotations

from .bm25_numpy import TIE_RTOL, Index, posting_bytes  # noqa: F401

COMMANDS = {"COUNT": (False, True), "TOP_10": (True, False),
            "TOP_10_COUNT": (True, True)}      # (hits asked, total asked)


class Game:
    """The collection inverted once (`bm25_numpy.Index`) beside its token
    array, for the four shapes' match sets."""

    def __init__(self, dataset: dict, bm25: dict):
        self.index = Index(dataset, bm25)
        self.toks, self.doc_of = dataset["toks"], dataset["doc_of"]
        self.n = self.index.n

    def match(self, terms, shape: str, adjacency: bool = True):
        """Bool over every document: does it match."""
        import numpy as np
        ix = self.index
        distinct = list(dict.fromkeys(int(t) for t in terms))
        held = np.zeros((len(distinct), self.n), dtype=bool)
        for row, t in zip(held, distinct):
            row[ix.docs[ix.start[t]:ix.start[t + 1]]] = True
        if shape in ("term", "union"):
            return held.any(axis=0)
        if shape == "intersection" or not adjacency:
            return held.all(axis=0)
        # phrase: start positions p with toks[p + j] == terms[j] for every
        # slot j, all inside one document
        n = len(terms)
        p = np.flatnonzero(self.toks[:len(self.toks) - n + 1] == terms[0])
        for j in range(1, n):
            p = p[self.toks[p + j] == terms[j]]
        p = p[self.doc_of[p] == self.doc_of[p + n - 1]]
        out = np.zeros(self.n, dtype=bool)
        out[self.doc_of[p]] = True
        return out

    def answer(self, terms, shape: str, size: int,
               low_precision: bool = False, adjacency: bool = True):
        """(match mask, float64 scores over every document, top ids, top
        scores): the top-`size` of the match set, scored in bfloat16
        where `low_precision`."""
        matched = self.match(terms, shape, adjacency)
        scores, _ = self.index.score(terms, low_precision)
        ids, sc = self.index.topk(scores, matched, size)
        return matched, scores, ids, sc


def shape_faults(answer: dict, cmd: str, n_docs: int,
                 size: int) -> tuple[int, int]:
    """(wrong hits, wrong totals) of one answer by its command's shape."""
    want_hits, want_total = COMMANDS[cmd]
    hits, total = answer["hits"], answer["total"]
    if want_total:
        bad_total = int(not (isinstance(total, int) and total >= 0 and
                             answer.get("relation") == "eq"))
    else:
        bad_total = int(total is not None)
    bad = max(len(hits) - (size if want_hits else 0), 0)
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
    return bad, bad_total


def compare(answer: dict, query, game: Game, size: int,
            control=False) -> tuple[int, int, float]:
    """(wrong hits, wrong totals, largest relative score error) of one
    answer against the reference. Under a control the ANSWER is replaced
    by the control's own."""
    terms, shape, cmd = query
    want_hits, want_total = COMMANDS[cmd]
    matched, ref, top_ids, top_sc = game.answer(terms, shape, size)
    total = int(matched.sum())
    if control:
        c_matched, _, ids, sc = game.answer(
            terms, shape, size,
            low_precision=control in (True, "bf16"),
            adjacency=control not in (True, "no_adjacency"))
        answer = {"total": int(c_matched.sum()) if want_total else None,
                  "relation": "eq" if want_total else None,
                  "hits": [(str(int(i)), float(s))
                           for i, s in zip(ids, sc)] if want_hits else []}
    wrong, worst = 0, 0.0
    if want_hits:
        kth = float(top_sc[-1]) if len(top_sc) else 0.0
        inside = set(int(i) for i in top_ids)
        for hid, score in answer["hits"]:
            d = int(hid) if str(hid).isdigit() else -1
            if not (0 <= d < game.n) or not matched[d]:
                wrong += 1
                continue
            r = float(ref[d])
            worst = max(worst, abs(float(score) - r) / r)
            if d not in inside and r < kth * (1.0 - TIE_RTOL):
                wrong += 1
        wrong += abs(len(answer["hits"]) - min(size, total))
    bad_total = int(want_total and answer["total"] != total)
    return wrong, bad_total, worst


def check(ops, source, dataset, seed, check_spec, control=False, cfg=None):
    """({name: value}, answers compared in full). `ops` are the window's
    operations in the order they were sent; the k-th of a client is the
    k-th query the source gave that client."""
    import numpy as np
    size = source.size
    n_docs = int(dataset["n_docs"])
    nth = [0] * len(source.sent)
    answered = []
    wrong_hits = wrong_totals = 0
    for o in ops:
        k = nth[o["client"]]
        nth[o["client"]] += 1
        if not o["ok"]:
            continue
        query = source.sent[o["client"]][k]
        answered.append((o["answer"], query))
        if not control:
            h, t = shape_faults(o["answer"], query[2], n_docs, size)
            wrong_hits += h
            wrong_totals += t
    rng = np.random.default_rng([int(seed), 977])
    n = min(int(check_spec["sample"]), len(answered))
    pick = rng.choice(len(answered), n, replace=False) if n else []
    game = Game(dataset, cfg["bm25"])
    worst = 0.0
    for i in pick:
        w, t, e = compare(answered[int(i)][0], answered[int(i)][1], game,
                          size, control)
        wrong_hits += w
        wrong_totals += t
        worst = max(worst, e)
    return ({"wrong_hits": wrong_hits, "wrong_totals": wrong_totals,
             "score_rel_err_max": worst}, n)
