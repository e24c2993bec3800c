#!/usr/bin/env python3
"""How far the profiler's device trace reaches past the host-clock window
that `benchmark/launcher.py` divides the device's busy time by.

The launcher stamps its window behind `jax.profiler.start_trace` and in
front of `stop_trace`, and `benchmark/trace.py` takes the union of every
device operation in the trace, so whatever the trace holds outside the two
stamps counts as busy time of a window it is no part of. This keeps one
device busy without a gap (a loop of matrix products a program, the next
program dispatched before the wait for the one in flight, as the step loop's
queued order does), traces it the launcher's way, and prints a JSON line a
round: `lead_s` (the first traced operation to the window's start),
`tail_s` (the window's end to the last), `busy_s` and `window_s` as the
launcher would report them, and what the two calls took.

    chiprun -- python3 scripts/trace_edges.py        (~1.5 min, one chip)

Alone in its process the edges read under 2 ms and 1 ms (my chip run, PR 60),
and `busy_s` still passes `window_s` by 0.2-0.7 ms at no idle time at all;
inside the benchmark's engine process they read 30-46 ms (PERF.md section 7,
From PR 60).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--products", default="100,15,250",
                    help="matrix products a program, one run each "
                         "(100 is about 72 ms on a v5e)")
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from benchmark import trace as trace_mod

    x = jnp.ones((4096, 4096), jnp.bfloat16)
    for products in (int(p) for p in args.products.split(",")):
        program = jax.jit(lambda a, n=products: jax.lax.fori_loop(
            0, n, lambda i, b: (b @ b) * 1e-3 + 1.0, a))
        jax.block_until_ready(program(x))
        t = time.monotonic()
        jax.block_until_ready(program(x))
        program_s = time.monotonic() - t
        stop = threading.Event()

        def keep_busy():
            queue = [program(x), program(x)]
            while not stop.is_set():
                queue.append(program(queue[-1]))
                jax.block_until_ready(queue.pop(0))
            jax.block_until_ready(queue)

        thread = threading.Thread(target=keep_busy)
        thread.start()
        time.sleep(0.5)
        for rnd in range(args.rounds):
            trace_dir = tempfile.mkdtemp(prefix="trace_edges.")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            t_call = time.monotonic()
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            mono_start = time.monotonic()
            with jax.profiler.TraceAnnotation("bench.clock_sync"):
                time.sleep(0.001)
            time.sleep(args.seconds)
            window_s = time.monotonic() - mono_start
            t_stop = time.monotonic()
            jax.profiler.stop_trace()
            stop_call_s = time.monotonic() - t_stop
            found = [os.path.join(root, f)
                     for root, _dirs, files in os.walk(trace_dir)
                     for f in files if f.endswith(".xplane.pb")]
            profile = jax.profiler.ProfileData.from_file(sorted(found)[-1])
            shutil.rmtree(trace_dir, ignore_errors=True)
            sync = trace_mod.find_host_event(profile, "bench.clock_sync")
            reduced = trace_mod.reduce(profile, window_s=window_s)
            plane = trace_mod.device_planes(profile)[0]
            events = trace_mod._events(
                trace_mod._line(plane, trace_mod.OPS_LINE))
            first = min(e[0] for e in events)
            last = max(e[1] for e in events)
            print(json.dumps({
                "products": products, "program_s": program_s, "round": rnd,
                "window_s": window_s, "busy_s": reduced["busy_s"],
                "lead_s": sync - first, "tail_s": last - (sync + window_s),
                "start_call_s": mono_start - t_call,
                "stop_call_s": stop_call_s}), flush=True)
        stop.set()
        thread.join()
    return 0


if __name__ == "__main__":
    sys.exit(main())
