#!/usr/bin/env python3
"""The server child of a `--trace 1` run: the same `pilosa_tpu.ctl.main` entry
a user starts, with `PILOSA_TPU_JAX_PROFILE=1` (the program's own host
annotations) and `jax.profiler` started on SIGUSR1 and stopped on SIGUSR2.
Only the process that holds the chip can trace it, and the program has no
switch of its own for that yet (PERF.md, Open questions).

The trace goes to $PBENCH_TRACE_DIR; `window.json` there says when the traced
window began and ended on CLOCK_MONOTONIC, which the harness's clients share.
"""

import json
import os
import signal
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.append(HERE)

from pbench.xplane import WINDOW_MARK  # noqa: E402  (imports no jax)


def main() -> int:
    os.environ["PILOSA_TPU_JAX_PROFILE"] = "1"
    trace_dir = os.environ["PBENCH_TRACE_DIR"]
    start, stop = threading.Event(), threading.Event()
    signal.signal(signal.SIGUSR1, lambda *a: start.set())
    signal.signal(signal.SIGUSR2, lambda *a: stop.set())

    def tracer():
        start.wait()
        import jax

        # Runtime and program annotations only. The Python tracer would add
        # an event for every call (millions in 5 s: a query loops over 960
        # slices in Python), slow the server it measures by half and make the
        # trace take minutes to read.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        # The profiler records until stop_trace has taken effect, some tens
        # of milliseconds past t1: the annotation marks the window on the
        # trace's own clock, and the reduction cuts the device's ops to it.
        with jax.profiler.TraceAnnotation(WINDOW_MARK):
            t0 = time.monotonic()
            stop.wait()
            t1 = time.monotonic()
        jax.profiler.stop_trace()
        tmp = os.path.join(trace_dir, "window.json.tmp")
        with open(tmp, "w") as f:
            json.dump({"window_s": t1 - t0, "t0": t0, "t1": t1,
                       "stop_trace_s": time.monotonic() - t1}, f)
        os.replace(tmp, os.path.join(trace_dir, "window.json"))

    threading.Thread(target=tracer, name="pbench-tracer", daemon=True).start()
    from pilosa_tpu.ctl.main import main as ctl_main

    return ctl_main(sys.argv[1:])


if __name__ == "__main__":
    sys.exit(main())
