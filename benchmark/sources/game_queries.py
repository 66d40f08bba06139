"""Query-set kind `game_queries`: Search-Benchmark-the-Game's `queries.txt`
as an endless seeded stream, each (query, shape, command) sent once.

A query is two to four CONSECUTIVE tokens of one uniformly drawn target
article (names, titles and noun phrases of the AOL log are such spans of
the text they look for): mostly an occurrence of a collocation of that
length in it (another article if it holds none), else any span there whose
first and last token are not among the corpus's most frequent terms. It
is sent in ONE of upstream's four shapes

  term          its rarest token alone        {"match": {f: "c"}}
  intersection  +a +b +c                      {"match": {f: {"query": "a b c", "operator": "and"}}}
  union         a b c                         {"match": {f: "a b c"}}
  phrase        "a b c"                       {"match_phrase": {f: "a b c"}}

under ONE of upstream's three commands, as an Elasticsearch user sends
them (the query set's `commands`: COUNT = size 0 with the exact total,
TOP_10 = ten hits and NO total, TOP_10_COUNT = both). An operation's key
is `<shape>.<command>`, twelve keys, so `window.by_statement` reads by
both; `distinct_ops()` lists `warmup_per_key` operations of every key
for the warm-up, drawn from a stream of their own.

The source keeps what each client sent, in order (`sent[client]`:
(token ids, shape, command)), for the reference. What the query set
gives: `lengths`, `collocation_share`, `function_words`, `shapes`,
`commands`, `warmup_per_key`.
"""

from __future__ import annotations

import json


class Source:
    def __init__(self, qset: dict, traffic: dict, dataset: dict, seed: int,
                 salt: int = 37):
        import numpy as np
        self.np = np
        self.toks, self.bounds = dataset["toks"], dataset["bounds"]
        self.colloc_at = dataset["colloc_at"]
        self.colloc_len = dataset["colloc_len"]
        self.words = dataset["words"]
        self.n_docs = int(dataset["n_docs"])
        self.n_func = int(qset["function_words"])
        self._lengths = sorted((int(k), float(v))
                               for k, v in qset["lengths"].items())
        self._colloc_p = float(qset["collocation_share"])
        self._shapes = list(qset["shapes"].items())
        self.commands = dict(qset["commands"])
        self.size = max(int(c["size"]) for c in self.commands.values())
        self._per_key = int(qset["warmup_per_key"])
        self.path = f"/{dataset['params']['index']}/_search"
        self.field = dataset["params"]["field"]
        clients = int(traffic["clients"])
        self._rng = [np.random.default_rng([seed, salt, c])
                     for c in range(clients)]
        self._warm_rng = np.random.default_rng([seed, salt, 1 << 20])
        self.sent: list[list] = [[] for _ in range(clients)]
        self.warm: list = []
        self._seen: set = set()
        self.keys = [f"{s}.{c}" for s, _ in self._shapes
                     for c in self.commands]

    def _span(self, rng, n: int):
        """Token ids of one query of `n` tokens, in text order."""
        np = self.np
        while True:
            d = int(rng.integers(self.n_docs))
            lo, hi = int(self.bounds[d]), int(self.bounds[d + 1])
            if rng.random() < self._colloc_p:
                a, b = np.searchsorted(self.colloc_at, [lo, hi])
                at = self.colloc_at[a:b][self.colloc_len[a:b] == n]
            else:
                at = lo + np.flatnonzero(
                    (self.toks[lo:hi - n + 1] >= self.n_func) &
                    (self.toks[lo + n - 1:hi] >= self.n_func)) \
                    if hi - lo >= n else []
            if len(at):
                s = int(at[int(rng.integers(len(at)))])
                return [int(t) for t in self.toks[s:s + n]]

    def _query(self, rng, key: str = None):
        """(token ids, shape, command): of `key` if given, else drawn."""
        np = self.np
        while True:
            n = int(rng.choice([k for k, _ in self._lengths],
                               p=[p for _, p in self._lengths]))
            if key is None:
                shape = str(rng.choice([s for s, _ in self._shapes],
                                       p=[p for _, p in self._shapes]))
                cmd = str(rng.choice(list(self.commands)))
            else:
                shape, cmd = key.split(".")
            terms = self._span(rng, n)
            if shape == "term":
                terms = [max(terms)]      # ids are frequency ranks
            once = (tuple(terms), shape, cmd)
            if once not in self._seen:
                self._seen.add(once)
                return list(terms), shape, cmd

    def _op(self, q):
        terms, shape, cmd = q
        text = " ".join(self.words[t] for t in terms)
        if shape == "phrase":
            query = {"match_phrase": {self.field: text}}
        elif shape == "intersection":
            query = {"match": {self.field: {"query": text,
                                            "operator": "and"}}}
        else:
            query = {"match": {self.field: text}}
        body = {"query": query, **self.commands[cmd]}
        return f"{shape}.{cmd}", (self.path, json.dumps(body))

    def distinct_ops(self):
        """`warmup_per_key` operations of every key the stream can send."""
        if not self.warm:
            for key in self.keys:
                for _ in range(self._per_key):
                    self.warm.append(self._query(self._warm_rng, key))
        return [self._op(q) for q in self.warm]

    def next_op(self, client: int):
        q = self._query(self._rng[client])
        self.sent[client].append(q)
        return self._op(q)
