#!/usr/bin/env python3
"""A serened child with the timed path broken underneath, for
test_run_end_to_end.py: every 5th integer the wire encoder writes is altered where
it is produced. The harness
above it is unchanged and has to see `correct` come out false."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark.harness import serve_child  # noqa: E402


def break_answers():
    from serenedb_tpu.server import pgwire
    real_text = pgwire.pg_text
    seen = {"n": 0}

    def bad_text(v, t, db):
        out = real_text(v, t, db)
        if out is not None and out.isdigit() and int(out) > 100:
            seen["n"] += 1
            if seen["n"] % 5 == 0:
                return str(int(out) + 1).encode()
        return out

    pgwire.pg_text = bad_text


if __name__ == "__main__":
    break_answers()
    serve_child.main(sys.argv[1:])
