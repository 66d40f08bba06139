"""The bytes a scan has to read, whatever implements it: rows times, for
each column the statement references, the narrowest 1/2/4/8-byte integer
that holds the column's range (a string column counts as its dictionary
code). This is the work, not the kernel: the device's frame-of-reference
tiles (`bench.py:184`) can do no better, so a roofline share built on it
cannot pass 100% by counting generously.
"""

from __future__ import annotations


def column_width(lo: int, hi: int) -> int:
    span = max(int(hi) - int(lo), 0)
    for width in (1, 2, 4):
        if span < (1 << (8 * width)):
            return width
    return 8


def referenced_columns(spec: dict) -> list[str]:
    cols = [c for c, _, _ in spec.get("where", [])]
    cols += list(spec.get("group_by", []))
    cols += [arg for _, arg in spec["select"] if arg != "*"]
    return sorted(set(cols))


def scan_bytes(spec: dict, n_rows: int, widths: dict) -> int:
    return n_rows * sum(widths[c] for c in referenced_columns(spec))


def table_widths(columns: dict) -> dict:
    return {name: column_width(a.min(), a.max())
            for name, a in columns.items()}
