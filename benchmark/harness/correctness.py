"""What decides `correct`: numbers, each beside a limit of its own (the
limits are in the configuration file; PERF.md gives the readings each was
set from).

Most numbers come from the cell's reference (`benchmark/references/
<name>.py`, chosen by the query set's `reference` key): its `check`
compares what the clients read in the timed window with the plain
reference and returns ({name: value}, answers compared). Two the harness
adds itself (`window_numbers`), so that a window in which statements fail
or programs compile cannot pass on the answers that were left to compare.
The CONTROL is the reference put in the program's place, computed in the
nearest precision below the one the configuration states; it must come
out NOT correct (tests/, PERF.md).
"""

from __future__ import annotations

import math


def window_numbers(ops: list, programs_built: int) -> dict:
    """`failed_ops`: operations of the window that were refused, errored or
    never answered (the reference never sees them). `programs_built_in_
    window`: executables jax had to make or load for a new shape while the
    window ran (the child wrapper counts them): the contract wants none."""
    return {"failed_ops": sum(1 for o in ops if not o["ok"]),
            "programs_built_in_window": int(programs_built)}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(`correct`, {name: {"value", "limit"}}). A number without a limit
    or a limit without a number is an error, not a pass."""
    if set(numbers) != set(limits):
        raise ValueError(f"numbers {sorted(numbers)} and limits "
                         f"{sorted(limits)} do not pair up")
    out, ok = {}, True
    for name in sorted(numbers):
        v, lim = numbers[name], limits[name]
        out[name] = {"value": v, "limit": lim}
        if not (isinstance(v, (int, float)) and math.isfinite(v)
                and v <= lim):
            ok = False
    return ok, out
