"""What a serving process spends on the host, read from the process itself:
CPU seconds by thread class and the collector's pauses. The engine and the
gateway both serve these (docs/tracing.md "The host").

* **CPU seconds by thread class** (`cpu_seconds`) are read when somebody
  asks, inside the `/api/health` and `/metrics` handlers: one
  `time.process_time()` and one `clock_gettime` of each live Python thread's
  own CPU clock. The hot paths pay nothing. All of the process's Python
  shares one GIL, so over a window `process` near one core with several
  classes busy is a saturated GIL, and one class near one core is a
  saturated thread.
* **The collector** (`GC`): `gc.callbacks` stamps every collection's start
  and stop with the step loop's clock. A collection holds the GIL, so it
  stalls every thread of the process for as long as it runs, whichever
  thread set it off; the step loop charges it to the record that was open
  (`gc_s`, engine/stepstats.py).
"""

from __future__ import annotations

import gc
import threading
import time


def cpu_seconds(classes: dict[str, tuple[str, ...]],
                current: str | None = None) -> dict[str, float]:
    """Cumulative CPU seconds of this process (`process`), of its live
    threads by class, and `other` = process less the classes (threads that
    are not Python's, XLA's among them, and threads that have ended).

    `classes` maps a class to the prefixes of its threads' names; `current`
    is the class of the calling thread (an event loop has no name of its
    own: the handler that asks runs on it). A class's figure is the sum
    over its LIVE threads, so it falls when one of them ends; the classes
    named here (a step loop, an executor's pool, an event loop) live as long
    as the process serves."""
    out = {"process": time.process_time()}
    out.update(dict.fromkeys(classes, 0.0))
    if current is not None:
        out.setdefault(current, 0.0)
    me = threading.get_ident()
    for t in threading.enumerate():
        if t.ident == me and current is not None:
            cls = current
        else:
            cls = next((c for c, prefixes in classes.items()
                        if t.name.startswith(prefixes)), None)
        if cls is None or t.ident is None or not t.is_alive():
            continue
        try:
            out[cls] += time.clock_gettime(
                time.pthread_getcpuclockid(t.ident))
        except (OSError, ValueError, OverflowError):
            pass  # the thread ended between the listing and the read
    named = sum(v for k, v in out.items() if k != "process")
    out["other"] = max(0.0, out["process"] - named)
    return {k: round(v, 6) for k, v in out.items()}


class GcClock:
    """Cumulative collections by generation and the seconds they took."""

    def __init__(self, now=time.perf_counter):
        self._now = now
        self.collections_total = [0, 0, 0]
        self.seconds_total = 0.0
        self._t0: float | None = None

    def __call__(self, phase: str, info: dict) -> None:
        # collections do not nest and run with the GIL held: no lock
        if phase == "start":
            self._t0 = self._now()
        elif self._t0 is not None:
            self.seconds_total += self._now() - self._t0
            self._t0 = None
            self.collections_total[min(info.get("generation", 2), 2)] += 1

    def snapshot(self) -> dict:
        return {"collections_total": {str(g): n for g, n
                                      in enumerate(self.collections_total)},
                "seconds_total": round(self.seconds_total, 6)}


GC = GcClock()


def watch_gc() -> GcClock:
    """The process's one collector clock, listening from the first call."""
    if GC not in gc.callbacks:
        gc.callbacks.append(GC)
    return GC
