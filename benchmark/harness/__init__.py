"""The benchmark's general code: everything here is shared by every cell.

What belongs to ONE configuration, traffic mix, query set or per-layer
metric is a file of its own elsewhere under `benchmark/`, found by the
name `BENCHMARK.json` gives it (see `benchmark/README.md`).
"""
