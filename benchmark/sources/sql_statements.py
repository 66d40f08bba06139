"""Query-set kind `sql_statements`: statement templates with their
reference specs, an optional `grid` of placeholder values, and how each
connection picks its next statement (the traffic file's `pick`):

  "file_order"  the set in file order, round after round
  {"statement": "uniform", "grid": {"r": {"zipf": 1.2}}}
                template uniform, each grid variable Zipf over its values

A query-set kind is a module of its own under `benchmark/sources/`, found
by the query set's `kind`; it gives `Source(qset, traffic, dataset, seed)`
with `next_op(client) -> (key, payload)`, `distinct_ops()` (every
operation the window can send, for the warm-up; None if they cannot be
listed) and whatever its reference's `check` reads.
"""

from __future__ import annotations

import copy
import itertools


def _fill(obj, params: dict):
    """`{name}` placeholders in every string of a JSON value; a string
    that was exactly one placeholder takes the parameter's own type."""
    if isinstance(obj, str):
        if obj.startswith("{") and obj.endswith("}") and \
                obj[1:-1] in params:
            return params[obj[1:-1]]
        return obj.format(**params) if "{" in obj else obj
    if isinstance(obj, list):
        return [_fill(x, params) for x in obj]
    if isinstance(obj, dict):
        return {k: _fill(v, params) for k, v in obj.items()}
    return obj


def _zipf_weights(n: int, s: float):
    import numpy as np
    w = np.arange(1, n + 1, dtype=np.float64) ** -s
    return w / w.sum()


class Source:
    """Concrete statements of a `sql_statements` query set."""

    def __init__(self, qset: dict, traffic: dict, dataset: dict, seed: int,
                 salt: int = 11):
        import numpy as np
        grid = qset.get("grid", {})
        names = sorted(grid)
        self.statements = []          # (key, sql, ref spec)
        self._index = {}              # (template i, grid choice) -> stmt i
        for ti, st in enumerate(qset["statements"]):
            for combo in itertools.product(*[range(len(grid[n]))
                                             for n in names]):
                params = dict(dataset["params"])
                params.update({n: grid[n][c] for n, c in zip(names, combo)})
                key = st["id"] + "".join(f".{n}={params[n]}" for n in names)
                self._index[(ti, combo)] = len(self.statements)
                self.statements.append(
                    (key, st["sql"].format(**params),
                     _fill(copy.deepcopy(st["ref"]), params)))
        self.by_key = {k: (sql, ref) for k, sql, ref in self.statements}
        clients = int(traffic["clients"])
        self._pick = traffic["pick"]
        self._cursor = [0] * clients
        self._n_templates = len(qset["statements"])
        self._grid_sizes = [len(grid[n]) for n in names]
        self._rng = [np.random.default_rng([seed, salt, c])
                     for c in range(clients)]
        self._weights = {
            n: _zipf_weights(len(grid[n]),
                             float(self._pick["grid"][n]["zipf"]))
            for n in names} if isinstance(self._pick, dict) else None
        self._names = names

    def distinct_ops(self):
        return [(k, sql) for k, sql, _ in self.statements]

    def next_op(self, client: int):
        if self._pick == "file_order":
            i = self._cursor[client]
            self._cursor[client] = (i + 1) % len(self.statements)
        else:
            rng, w = self._rng[client], self._weights
            ti = int(rng.integers(self._n_templates))
            combo = tuple(int(rng.choice(size, p=w[n]))
                          for n, size in zip(self._names, self._grid_sizes))
            i = self._index[(ti, combo)]
        key, sql, _ = self.statements[i]
        return key, sql
