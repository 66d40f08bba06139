"""Dataset `msmarco_dense`: the MS MARCO passages of `datasets/msmarco.py`
(same seed, same text) with one 768-d float32 embedding a passage, made,
not fetched.

There is no encoder here, so the embeddings are made as sentence
embeddings of a bi-encoder look (`SHAPE` below, every figure a choice
listed under the configuration's `assumed`): unit vectors around `topics`
topic directions with Zipf topic sizes, a within-topic Gaussian spread
that leaves a mean cosine of about 0.8 to the topic's own direction, and
a component every vector shares (anisotropy: a mean pairwise cosine of
about 0.25).

`generate(cfg, seed, workdir)` writes ONE parquet file (`_id`, `_source`,
`body`, `emb` as FixedSizeList<float>[dims]) and returns the load
statements, what `msmarco.generate` returns for the text, and the array
the plain reference and the question source read (`emb`).
"""

from __future__ import annotations

import os

from . import msmarco

SHAPE = {
    # topic directions, Zipf sizes (exponent 1): a few topics hold
    # thousands of passages at 1M, most a few dozen
    "topics": 4096, "topic_zipf": 1.0,
    # v = shared * m + topic * t_j + spread * g, then normalised; the
    # three squares add up to 1: cos(v, its topic's direction) = 0.8,
    # cos between passages of different topics = 0.25, of one topic 0.64
    "shared": 0.5, "topic": 0.6245, "spread": 0.6,
}

#: rows made, normalised and written at a time
BLOCK = 65536


def embeddings(n: int, dims: int, seed: int):
    """(n, dims) float32 unit vectors, in bulk, a block at a time."""
    import numpy as np
    rng = np.random.default_rng([seed, 31])
    topics = rng.standard_normal((SHAPE["topics"], dims))
    topics /= np.linalg.norm(topics, axis=1, keepdims=True)
    mean = rng.standard_normal(dims)
    mean /= np.linalg.norm(mean)
    p = np.arange(1, SHAPE["topics"] + 1, dtype=np.float64) \
        ** -SHAPE["topic_zipf"]
    cdf = np.cumsum(p / p.sum())
    cdf[-1] = 1.0
    topic_of = np.searchsorted(cdf, rng.random(n), side="right") \
        .astype(np.int32)
    centre = (SHAPE["shared"] * mean[None, :] +
              SHAPE["topic"] * topics).astype(np.float32)
    emb = np.empty((n, dims), dtype=np.float32)
    scale = np.float32(SHAPE["spread"] / np.sqrt(dims))
    for at in range(0, n, BLOCK):
        blk = emb[at:at + BLOCK]
        rng.standard_normal(out=blk, dtype=np.float32)
        blk *= scale
        blk += centre[topic_of[at:at + BLOCK]]
        blk /= np.linalg.norm(blk, axis=1, keepdims=True)
    return emb, topic_of


def generate(cfg: dict, seed: int, workdir: str) -> dict:
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq
    dims = int(cfg["dims"])
    text = msmarco.generate(cfg, seed, workdir)
    n = int(text["n_docs"])
    emb, topic_of = embeddings(n, dims, seed)
    text_path = os.path.join(workdir, "passages.parquet")
    path = os.path.join(workdir, "passages_dense.parquet")
    tbl = pq.read_table(text_path)
    os.remove(text_path)
    # FixedSizeList<float>[dims] with non-nullable elements: parquet then
    # keeps no definition level per number (twelve times faster to read)
    vec_type = pa.list_(pa.field("element", pa.float32(), nullable=False),
                        dims)
    schema = tbl.schema.append(pa.field("emb", vec_type))
    with pq.ParquetWriter(path, schema, compression={
            **{name: "snappy" for name in tbl.schema.names},
            "emb": "NONE"}, use_dictionary=False,
            write_statistics=False) as w:
        for at in range(0, n, BLOCK):
            blk = emb[at:at + BLOCK]
            vec = pa.FixedSizeListArray.from_arrays(
                pa.array(blk.reshape(-1)), type=vec_type)
            w.write_table(tbl.slice(at, len(blk)).append_column(
                pa.field("emb", vec_type), vec))
    del tbl
    out = dict(text)
    out["load"] = [
        'CREATE TABLE passages ("_id" VARCHAR, "_source" VARCHAR, '
        f"body VARCHAR, emb VECTOR({dims}))",
        f"COPY passages FROM '{path}' (FORMAT parquet)",
        "CREATE INDEX passages_body ON passages USING inverted (body) "
        "WITH (tokenizer = 'simple')",
        "CREATE INDEX passages_emb ON passages USING ivf (emb) "
        "WITH (type = 'flat', metric = 'cos')"]
    out["params"] = {"index": "passages", "field": "body",
                     "vector_field": "emb"}
    out["emb"] = emb
    out["topic_of"] = topic_of
    return out
