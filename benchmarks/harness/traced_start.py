"""`python -m tigerbeetle_tpu start ...` with a profiler window.

Only the process that holds the chip can trace it, and `start --backend
device` has no trace option of its own, so a `--trace 1` run starts the
server through this wrapper: the same `cli.main`, plus a thread that opens
and closes ONE jax.profiler window when the benchmark says so on stdin
(`start <dir>` / `stop`), and answers each with a `[trace] {...}` line
that stamps both clocks. `--trace 0` runs never use this file.
"""

import json
import sys
import threading
import time


def _control() -> None:
    import jax

    for line in sys.stdin:
        words = line.split()
        if not words:
            continue
        out = {"command": words[0], "ok": True,
               "asked_monotonic": time.monotonic()}
        try:
            if words[0] == "start":
                # no Python tracer: it would log every call of the event
                # loop and slow the server it is measuring
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                jax.profiler.start_trace(words[1], profiler_options=options)
            elif words[0] == "stop":
                jax.profiler.stop_trace()
            else:
                out["ok"] = False
        except Exception as e:  # the answer line carries the failure
            out.update(ok=False, error=f"{type(e).__name__}: {e}")
        out["time_ns"] = time.time_ns()
        out["monotonic"] = time.monotonic()
        print("[trace] " + json.dumps(out), flush=True)


if __name__ == "__main__":
    from tigerbeetle_tpu.cli import main

    threading.Thread(target=_control, daemon=True).start()
    sys.exit(main(sys.argv[1:]))
