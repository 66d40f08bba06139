#!/usr/bin/env python3
"""The one process that holds the chip: `serenedb_tpu.serened.main`,
unchanged, in the main thread — plus a control thread of the benchmark's
own that, on the parent's word (one line on stdin), starts or stops a
`jax.profiler` trace of THIS process or reads the device's memory
statistics, or says how many programs jax built. Only the process that holds the chip can do either, and this
does so without a line of the program changed.

    python3 benchmark/harness/serve_child.py <datadir>

Replies are single stdout lines `BENCHCTL {json}` (the parent reads the
child's log). A command this thread cannot carry out replies
`{"ok": false, "error": ...}`; it never takes the server down.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time


#: programs this process built since start, counted from OUTSIDE the
#: program through jax.monitoring: `built` = every executable jax had to
#: make or load for a new shape (a trace + a compile-or-cache-load; the
#: kernels that bypass the program's own ledger are counted too),
#: `compiled` = those the persistent cache did not hold
_BUILT = {"built": 0, "compiled": 0}
_TRACE = {"t0": 0.0}


def _count_programs() -> None:
    import jax.monitoring as mon

    def on_duration(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            _BUILT["built"] += 1

    def on_event(event, **kw):
        if event == "/jax/compilation_cache/cache_misses":
            _BUILT["compiled"] += 1

    mon.register_event_duration_secs_listener(on_duration)
    mon.register_event_listener(on_event)


def _reply(obj: dict) -> None:
    print("BENCHCTL " + json.dumps(obj), flush=True)


def _control() -> None:
    for line in sys.stdin:
        parts = line.split()
        if not parts:
            continue
        tag, cmd, args = parts[0], parts[1], parts[2:]
        try:
            import jax
            if cmd == "trace_start":
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                jax.profiler.start_trace(args[0], profiler_options=opts)
                _TRACE["t0"] = time.monotonic()
                _reply({"tag": tag, "ok": True})
            elif cmd == "trace_stop":
                traced_s = time.monotonic() - _TRACE["t0"]
                jax.profiler.stop_trace()
                _reply({"tag": tag, "ok": True, "traced_s": traced_s})
            elif cmd == "programs":
                _reply({"tag": tag, "ok": True, **_BUILT})
            elif cmd == "memstats":
                devs = jax.local_devices()
                stats = [d.memory_stats() or {} for d in devs]
                _reply({"tag": tag, "ok": True,
                        "platform": devs[0].platform,
                        "kind": devs[0].device_kind, "count": len(devs),
                        "peak_bytes_in_use": [
                            int(s.get("peak_bytes_in_use", 0))
                            for s in stats],
                        "bytes_in_use": [int(s.get("bytes_in_use", 0))
                                         for s in stats],
                        "bytes_limit": [int(s.get("bytes_limit", 0))
                                        for s in stats]})
            else:
                _reply({"tag": tag, "ok": False,
                        "error": f"unknown command {cmd!r}"})
        except Exception as e:  # noqa: BLE001 — reported to the parent
            _reply({"tag": tag, "ok": False,
                    "error": f"{type(e).__name__}: {e}"})


def main(argv) -> None:
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path.insert(0, root)
    from serenedb_tpu import serened
    _count_programs()
    threading.Thread(target=_control, name="bench-control",
                     daemon=True).start()
    serened.main([argv[0], "--pg-port", "0", "--http-port", "0"])


if __name__ == "__main__":
    main(sys.argv[1:])
