"""Query-set kind `match_questions`: an endless seeded stream of
natural-language questions, each sent once, as `_search` match queries.

A question is built as MS MARCO's are: it is answerable — two to five
content terms drawn from ONE target passage, rarer terms likelier —
and stands behind a frame of function words (the collection's most
frequent terms, drawn without repetition by their frequency): "what is
the ... of ...". Every term of a question is distinct, so the count of its
terms is its key (`t2` ... `t15`): `window.by_statement` reads by query
length, and `distinct_ops()` lists a fixed number of questions per key
for the warm-up, built for that key from a stream of their own.

The source keeps what each client sent, in order (`sent[client]`, term
ids), for the reference. What the query set gives: `function_words`,
`content_terms` ({count: probability}), `function_mean`, `function_max`,
`size`, `warmup_per_key`.
"""

from __future__ import annotations

import json


class Source:
    def __init__(self, qset: dict, traffic: dict, dataset: dict, seed: int,
                 salt: int = 31):
        import numpy as np
        self.np = np
        self.toks, self.bounds = dataset["toks"], dataset["bounds"]
        self.words = dataset["words"]
        self.n_docs = int(dataset["n_docs"])
        self.n_func = int(qset["function_words"])
        prob = dataset["term_prob"]
        self._func_p = prob[:self.n_func] / prob[:self.n_func].sum()
        self._rarity = -np.log(prob)          # rarer terms likelier
        self._content = sorted((int(k), float(v)) for k, v in
                               qset["content_terms"].items())
        self._f_mean = float(qset["function_mean"])
        self._f_max = int(qset["function_max"])
        self._per_key = int(qset["warmup_per_key"])
        self.size = int(qset["size"])
        self.path = f"/{dataset['params']['index']}/_search"
        self.field = dataset["params"]["field"]
        clients = int(traffic["clients"])
        self._rng = [np.random.default_rng([seed, salt, c])
                     for c in range(clients)]
        self._warm_rng = np.random.default_rng([seed, salt, 1 << 20])
        self.sent: list[list] = [[] for _ in range(clients)]
        self.warm: list = []
        self.keys = [f"t{n}" for n in range(
            self._content[0][0], self._content[-1][0] + self._f_max + 1)]

    def _question(self, rng, n_terms: int = None):
        """Term ids of one question: function words first (the frame),
        then content terms in passage order."""
        np = self.np
        counts = [c for c, _ in self._content]
        while True:
            if n_terms is None:
                c = int(rng.choice(counts, p=[p for _, p in self._content]))
                f = min(int(rng.poisson(self._f_mean)), self._f_max)
            else:
                c = int(rng.integers(max(counts[0], n_terms - self._f_max),
                                     min(counts[-1], n_terms) + 1))
                f = n_terms - c
            d = int(rng.integers(self.n_docs))
            terms = np.unique(self.toks[self.bounds[d]:self.bounds[d + 1]])
            terms = terms[terms >= self.n_func]
            if len(terms) < c:
                continue          # a passage of function words: another
            w = self._rarity[terms]
            content = rng.choice(terms, c, replace=False, p=w / w.sum())
            frame = rng.choice(self.n_func, f, replace=False,
                               p=self._func_p)
            return [int(t) for t in frame] + [int(t) for t in content]

    def _op(self, terms: list):
        body = {"query": {"match": {self.field: " ".join(
            self.words[t] for t in terms)}}, "size": self.size}
        return f"t{len(terms)}", (self.path, json.dumps(body))

    def distinct_ops(self):
        """`warmup_per_key` questions of every key the stream can send."""
        if not self.warm:
            for key in self.keys:
                for _ in range(self._per_key):
                    self.warm.append(self._question(self._warm_rng,
                                                    int(key[1:])))
        return [self._op(terms) for terms in self.warm]

    def next_op(self, client: int):
        terms = self._question(self._rng[client])
        self.sent[client].append(terms)
        return self._op(terms)
