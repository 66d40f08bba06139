"""ClickBench `hits`, synthesized from `--seed`: six of its columns, in the
source's own types, with the cardinalities and skew of the real table.

There is no network and no `hits.parquet` here, so the table is made. What
it is made FROM are answers the real table gives to ClickBench's own
queries (Q0, Q1, Q2, Q4, Q5, Q7, Q8/Q9, Q12, Q15), as its repository and
the ClickHouse documentation print them; they are written here from
memory (`SOURCE`, below) and the configuration file lists them under
`assumed` for a session with a network to check. Two of them check
themselves: the 18 per-engine counts of Q7 add up to Q1's 630,500, and
their weighted sum to Q2's SUM(AdvEngineID) = 7,280,088.

How a column is made from them:

- a key column (UserID, SearchPhrase) is its ten (seven) most frequent
  keys at the source's counts, then a power-law tail `count(rank) ~
  rank^-s` whose exponent and length are FITTED so that the full table
  would hold the source's number of rows and of distinct keys
  (`fit_tail`). The run's table is a row sample of that population: each
  key's rows there are Poisson around `rows / source rows` of its full
  count, drawn not at random but by fixed quantiles, so that every seed
  gets the SAME multiset of per-key row counts (same distinct count, same
  group sizes, same shapes for the programs) in another order;
- RegionID belongs to the user (each user has one home region, drawn by
  the regions' row shares): Q8's users per region add up to little more
  than all users, so the real table's users hardly move either;
- AdvEngineID's 18 non-zero values keep their counts, scaled;
- SearchEngineID follows SearchPhrase (searches name an engine, other rows
  mostly do not); ResolutionWidth is a table of common screen widths
  with the source's mean. Those two are guesses and say so.

The seed decides which 62-bit hash is which user, the home regions, the
screen widths and the row order; never a size. Which user's rows carry
which phrase and engine is fixed (a constant generator), because the
program's shapes follow the number of distinct (UserID, SearchPhrase) and
(SearchEngineID, SearchPhrase) pairs: every seed finds every program in
the compile cache.
"""

from __future__ import annotations

import math
import os

#: answers of the real table (99,997,497 rows), from memory
SOURCE = {
    "rows": 99_997_497,
    "distinct_UserID": 17_630_976,                 # Q4
    "distinct_SearchPhrase": 6_019_103,            # Q5 (the empty one too)
    "rows_with_SearchPhrase": 13_172_392,          # Q12's filter
    # Q15: rows of the ten most active users
    "top_UserID_rows": [29097, 25333, 10597, 6669, 6408, 6196, 6019, 5990,
                        5209, 4906],
    # Q12: rows of the most frequent phrases
    "top_SearchPhrase_rows": [70263, 34675, 24580, 21647, 19707, 19195,
                              17284],
    # Q9: (RegionID, rows) of the ten largest regions
    "top_regions": [(229, 18295832), (2, 6687587), (208, 4261812),
                    (169, 3320229), (32, 1843721), (34, 1792369),
                    (184, 1755192), (42, 1542717), (107, 1516690),
                    (51, 1435578)],
    # Q7: AdvEngineID -> rows; sums to Q1's 630,500, weighted to Q2's 7,280,088
    "AdvEngineID_rows": {2: 404602, 27: 113167, 13: 45631, 45: 38960,
                         44: 9730, 3: 6896, 62: 5266, 52: 3554, 50: 938,
                         28: 836, 53: 350, 25: 343, 61: 158, 21: 38,
                         42: 20, 16: 7, 7: 3, 22: 1},
    "avg_ResolutionWidth": 1513.4879,              # Q2
}

#: guesses, not answers of the source (the configuration says so too)
N_REGIONS = 9000
#: screen widths and their shares: 2013's common ones and the odd values
#: the real column is known for; mean 1513.6
WIDTHS = [(1368, .20), (1638, .155), (1996, .10), (1280, .11), (1024, .06),
          (1920, .09), (1366, .07), (1440, .045), (1600, .04), (1680, .035),
          (1360, .015), (1152, .01), (2560, .008), (1087, .01), (800, .005),
          (0, .01), (1750, .02), (1295, .012), (2048, .005)]
#: share of search rows by SearchEngineID (2 leads, as in Q14's answer)
SEARCH_ENGINES = [(2, .66), (3, .17), (1, .04), (4, .03), (13, .02),
                  (5, .015), (8, .01)]
N_OTHER_ENGINES = 50          # the rest, ids 14..63, Zipf(1.0)
ENGINE_ON_PLAIN_ROWS = 0.02   # rows without a phrase that name an engine


def full_table(top: list, s: float, n_keys: int) -> tuple[float, float]:
    """(rows, distinct keys) the FULL table would hold under `top` + the
    tail `count(r) = top[-1] * (k / r)^s`, r = k+1 .. n_keys, each key's
    rows Poisson around its count."""
    import numpy as np
    k = len(top)
    c = top[-1] * (k / np.arange(k + 1, n_keys + 1, dtype=np.float64)) ** s
    return sum(top) + float(c.sum()), k + float((1.0 - np.exp(-c)).sum())


def fit_tail(top: list, total: int, distinct: int,
             most: int = 1 << 25) -> tuple[float, int]:
    """(s, n_keys) for which `full_table` gives `total` rows and `distinct`
    keys. Run once (`python3 benchmark/datasets/hits.py`); the
    configuration keeps the result under `fitted`."""
    import numpy as np
    k = len(top)
    rest = total - sum(top)
    r = np.arange(k + 1, most + 1, dtype=np.float64)

    def fitted(s: float):
        c = top[-1] * (k / r) ** s
        n = int(np.searchsorted(np.cumsum(c), rest)) + 1
        if n > len(c):          # so steep that the rows are never reached
            return n, float("inf")
        return n, k + float((1.0 - np.exp(-c[:n])).sum())

    lo, hi = 0.05, 1.5          # a steeper tail is longer: more keys seen
    for _ in range(24):
        mid = (lo + hi) / 2
        lo, hi = (lo, mid) if fitted(mid)[1] > distinct else (mid, hi)
    s = round((lo + hi) / 2, 4)
    return s, k + fitted(s)[0]


def _poisson_classes(mu: float, m: int) -> list[tuple[int, int]]:
    """`m` keys of mean `mu` rows each: [(rows, how many keys)], the
    Poisson law apportioned by largest remainder (rows >= 1 only)."""
    p, k, shares = math.exp(-mu), 0, []
    while k < 1000 and (p * m > 1e-3 or k <= mu):
        shares.append(p * m)
        k += 1
        p *= mu / k
    whole = [int(x) for x in shares]
    short = min(m, round(sum(shares))) - sum(whole)
    for i in sorted(range(len(shares)),
                    key=lambda i: whole[i] - shares[i])[:max(short, 0)]:
        whole[i] += 1
    return [(k, c) for k, c in enumerate(whole) if k >= 1 and c > 0]


def sample_counts(top: list, s: float, n_keys: int, share: float,
                  rows: int):
    """Per-key row counts (descending) of a `share` row sample of the
    population `top` + tail(s, n_keys), adding up to `rows` exactly. No
    randomness: the same multiset whatever the seed."""
    import numpy as np
    k = len(top)
    counts = [max(1, round(c * share)) for c in top]
    r = k + 1
    mu_of = lambda rank: top[-1] * (k / rank) ** s * share  # noqa: E731
    while r <= n_keys and mu_of(r) >= 30:     # heavy keys: their mean
        counts.append(round(mu_of(r)))
        r += 1
    light: dict[int, int] = {}
    while r <= n_keys:                        # light keys: Poisson classes
        end = min(n_keys + 1, max(r + 1, int(r * 1.01)))
        mid = math.sqrt(r * (end - 1)) if end - 1 > r else r
        for rows_k, n in _poisson_classes(mu_of(mid), end - r):
            light[rows_k] = light.get(rows_k, 0) + n
        r = end
    parts = [np.asarray(counts, np.int64)]
    for rows_k in sorted(light, reverse=True):
        parts.append(np.full(light[rows_k], rows_k, np.int64))
    out = np.concatenate(parts)
    # land on `rows` exactly: single-row keys come or go (a fit is not exact)
    gap = rows - int(out.sum())
    if gap > 0:
        out = np.concatenate([out, np.ones(gap, np.int64)])
    elif gap < 0:
        if int((out == 1).sum()) < -gap:
            raise ValueError("cannot trim the sample to its row count")
        out = out[:len(out) + gap]
    return out


def _region_shares():
    """(ids, shares) of all regions: the source's ten largest, then a
    power-law tail over the other ids up to N_REGIONS that takes the rest
    of the rows. Ids are fixed, whatever the seed."""
    import numpy as np
    top_ids = [r for r, _ in SOURCE["top_regions"]]
    top = [c for _, c in SOURCE["top_regions"]]
    n_tail = N_REGIONS - len(top)
    rest = SOURCE["rows"] - sum(top)
    r = np.arange(11, 11 + n_tail, dtype=np.float64)
    lo, hi = 0.5, 3.0
    for _ in range(50):
        s = (lo + hi) / 2
        lo, hi = (s, hi) if (top[-1] * (10 / r) ** s).sum() > rest \
            else (lo, s)
    tail = top[-1] * (10 / r) ** s
    taken = set(top_ids)
    # the other ids, 1.. in an order fixed by a constant: popular regions
    # are spread over the id range, as in the source
    others = np.array([i for i in range(1, N_REGIONS + 11)
                       if i not in taken][:n_tail])
    others = others[np.random.default_rng(20130701).permutation(n_tail)]
    shares = np.concatenate([top, tail]) / SOURCE["rows"]
    return np.concatenate([top_ids, others]).astype(np.int32), \
        shares / shares.sum()


def _phrase_text(i: int) -> str:
    """Phrase number i (1-based) as words: 2 to 6 pseudo-words, about 27
    characters on average (a guess at the source's Cyrillic phrases)."""
    x = (i * 2654435761) & 0xFFFFFFFF
    words = []
    for w in range(2 + x % 5):
        x = (x * 1103515245 + 12345) & 0x7FFFFFFF
        words.append("".join(_SYL[(x >> (5 * j)) % len(_SYL)]
                             for j in range(2 + (x >> 20) % 3)))
    return " ".join(words) + f" {i:x}"


_SYL = ["ka", "re", "lo", "mi", "su", "ta", "no", "vi", "ze", "do", "pa",
        "ri", "go", "le", "bu", "sh", "an", "or", "el", "ty"]


def _by_share(rng, table: list, n: int, dtype):
    """`n` draws from [(value, share)], shares made to sum to 1."""
    import numpy as np
    vals = np.array([v for v, _ in table], dtype)
    p = np.array([s for _, s in table], np.float64)
    return vals[rng.choice(len(vals), size=n, p=p / p.sum())]


def generate(cfg: dict, seed: int, workdir: str) -> dict:
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq
    n = int(cfg["rows"])
    share = n / SOURCE["rows"]
    fit = cfg["fitted"]
    rng = np.random.default_rng([seed, 3])
    fixed = np.random.default_rng(20130701)     # structure, not labels

    # UserID: the same per-user row counts for every seed; the seed names
    # the users (62-bit hashes, as the source's are hashes) and their homes
    u_counts = sample_counts(SOURCE["top_UserID_rows"], fit["UserID"]["s"],
                             fit["UserID"]["n_keys"], share, n)
    n_users = len(u_counts)
    hashes = rng.integers(0, 1 << 62, n_users, dtype=np.int64)
    user_of_row = np.repeat(np.arange(n_users, dtype=np.int32), u_counts)
    region_ids, region_p = _region_shares()
    home = region_ids[rng.choice(len(region_ids), size=n_users, p=region_p)]

    # SearchPhrase: code 0 is the empty phrase; the rest as for users
    n_search = round(SOURCE["rows_with_SearchPhrase"] * share)
    p_counts = sample_counts(SOURCE["top_SearchPhrase_rows"],
                             fit["SearchPhrase"]["s"],
                             fit["SearchPhrase"]["n_keys"], share, n_search)
    pid = np.zeros(n, np.int32)
    pid[:n_search] = np.repeat(
        np.arange(1, len(p_counts) + 1, dtype=np.int32), p_counts)
    phrase_pool = [""] + [_phrase_text(i)
                          for i in range(1, len(p_counts) + 1)]

    # AdvEngineID: the source's 18 values at their counts, scaled
    adv = np.zeros(n, np.int16)
    at = 0
    for value, c in SOURCE["AdvEngineID_rows"].items():
        k = max(1, round(c * share))
        adv[at:at + k] = value
        at += k

    # SearchEngineID follows the phrase; ResolutionWidth stands alone
    engines = SEARCH_ENGINES + [
        (14 + i, (1.0 - sum(s for _, s in SEARCH_ENGINES))
         / (i + 1) / sum(1 / (j + 1) for j in range(N_OTHER_ENGINES)))
        for i in range(N_OTHER_ENGINES)]
    seid = np.where(fixed.random(n) < ENGINE_ON_PLAIN_ROWS,
                    _by_share(fixed, engines, n, np.int16),
                    np.int16(0)).astype(np.int16)
    seid[:n_search] = _by_share(fixed, engines, n_search, np.int16)
    width = _by_share(rng, WIDTHS, n, np.int16)

    # pair the columns (users with phrases: fixed; the others: the seed),
    # then order the rows by the seed
    user_of_row = user_of_row[fixed.permutation(n)]
    order = rng.permutation(n)
    uid = hashes[user_of_row][order]
    region = home[user_of_row][order]
    pid, seid = pid[order], seid[order]
    adv = adv[rng.permutation(n)]

    phrases = pa.DictionaryArray.from_arrays(
        pa.array(pid), pa.array(phrase_pool, pa.string()))
    path = os.path.join(workdir, "hits.parquet")
    pq.write_table(pa.table({
        "UserID": uid, "RegionID": region, "AdvEngineID": adv,
        "SearchPhrase": phrases.cast(pa.string()),
        "SearchEngineID": seid, "ResolutionWidth": width}),
        path, compression="snappy")
    return {
        # the source's own types (ClickBench's create.sql for PostgreSQL)
        "load": [
            'CREATE TABLE hits ("UserID" BIGINT, "RegionID" INTEGER, '
            '"AdvEngineID" SMALLINT, "SearchPhrase" TEXT, '
            '"SearchEngineID" SMALLINT, "ResolutionWidth" SMALLINT)',
            f"COPY hits FROM '{path}' (FORMAT parquet)"],
        "count": ("SELECT count(*) FROM hits", n),
        # what the plain reference reads: integer columns as generated
        # (widened: it computes in int64 whatever the table stores); the
        # string column as dictionary codes + the dictionary
        "columns": {"UserID": uid, "RegionID": region,
                    "AdvEngineID": adv.astype(np.int32),
                    "SearchPhrase": pid,
                    "SearchEngineID": seid.astype(np.int32),
                    "ResolutionWidth": width.astype(np.int32)},
        "dictionaries": {"SearchPhrase": phrase_pool},
        # values a query template may name as {key}: a mid-rank user, so
        # that Q19's point lookup returns some tens of rows
        "params": {"uid_rank1000": int(hashes[1000])},
        "bytes_written": os.path.getsize(path),
    }


if __name__ == "__main__":
    for col, top, total, distinct in (
            ("UserID", SOURCE["top_UserID_rows"], SOURCE["rows"],
             SOURCE["distinct_UserID"]),
            ("SearchPhrase", SOURCE["top_SearchPhrase_rows"],
             SOURCE["rows_with_SearchPhrase"],
             SOURCE["distinct_SearchPhrase"] - 1)):
        s_, n_ = fit_tail(top, total, distinct)
        print(col, {"s": s_, "n_keys": n_}, full_table(top, s_, n_))
