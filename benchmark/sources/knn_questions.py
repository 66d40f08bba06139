"""Query-set kind `knn_questions`: an endless seeded stream of question
EMBEDDINGS, each sent once, as `_search` knn queries.

A question is made as a bi-encoder's query embedding relates to its
answer's: the embedding of ONE target passage plus Gaussian noise,
renormalised — so the target is the nearest passage for most questions
and the other passages of its topic compete for the other nine places.
It is sent as JSON numbers with the digits a float32 needs to read back
as itself (nine significant: about 9 KB a request at 768 dimensions).

One key, `knn`. `distinct_ops()` lists `warmup_per_key` questions from a
stream of their own for the (serial) warm-up; the batch rungs above one
question are built and run once by CREATE INDEX itself. The source keeps
what each client sent, in order (`sent[client]`, float32 arrays), for the
reference. What the query set gives: `k`, `num_candidates`, `size`,
`noise`, `warmup_per_key`.
"""

from __future__ import annotations


class Source:
    def __init__(self, qset: dict, traffic: dict, dataset: dict, seed: int,
                 salt: int = 37):
        import numpy as np
        self.np = np
        self.emb = dataset["emb"]
        self.n_docs = int(dataset["n_docs"])
        self.dims = int(self.emb.shape[1])
        self.k = int(qset["k"])
        self.cand = int(qset["num_candidates"])
        self.size = int(qset["size"])
        self._sigma = float(qset["noise"]) / float(np.sqrt(self.dims))
        self._per_key = int(qset["warmup_per_key"])
        self.path = f"/{dataset['params']['index']}/_search"
        self.field = dataset["params"]["vector_field"]
        clients = int(traffic["clients"])
        self._rng = [np.random.default_rng([seed, salt, c])
                     for c in range(clients)]
        self._warm_rng = np.random.default_rng([seed, salt, 1 << 20])
        self.sent: list[list] = [[] for _ in range(clients)]
        self.warm: list = []
        self.keys = ["knn"]
        self._head = ('{"knn": {"field": "%s", "query_vector": ['
                      % self.field)
        self._tail = ('], "k": %d, "num_candidates": %d}, "size": %d}'
                      % (self.k, self.cand, self.size))

    def _question(self, rng):
        np = self.np
        d = int(rng.integers(self.n_docs))
        q = self.emb[d] + rng.standard_normal(
            self.dims, dtype=np.float32) * np.float32(self._sigma)
        return (q / np.linalg.norm(q)).astype(np.float32)

    def _op(self, q):
        body = self._head + ",".join(self.np.char.mod("%.9g", q)) + \
            self._tail
        return "knn", (self.path, body)

    def distinct_ops(self):
        if not self.warm:
            self.warm = [self._question(self._warm_rng)
                         for _ in range(self._per_key)]
        return [self._op(q) for q in self.warm]

    def next_op(self, client: int):
        q = self._question(self._rng[client])
        self.sent[client].append(q)
        return self._op(q)
