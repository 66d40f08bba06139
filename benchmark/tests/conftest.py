"""Tests of the harness. They run on the CPU backend (no chip here), so
they say whether the yardstick computes what it says, never how fast
anything is. Run them with:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

Nothing here touches the TPU library at import; every child process has a
timeout and takes port 0.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
