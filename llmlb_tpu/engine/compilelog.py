"""A ledger of every program this process builds.

JAX reports each stage of a build through ``jax.monitoring``: tracing a
function to a jaxpr, lowering the jaxpr to an MLIR module, and the backend
compile — which is either XLA compiling or a fetch from the persistent
compilation cache; a new shape either way, and every build ends in exactly
one such event. The listeners below are registered once, when this module is
imported. They are process-global and cannot be taken back, so the ledger is
module-level and a reader (``EngineMetrics``) keeps the counters it saw when
it was made and serves the difference.

The trace events nest (one ``jit`` call traces its inner functions too), so
``seconds_total`` are sums of events, not wall time: the wall time of a build
made inside a scheduler step is that step's own span (engine/stepstats.py),
and such a build carries the step's ``seq``.

``repeat_builds_total`` counts a build whose ``fun_name`` was already built
with the same outcome (compiled, or fetched). The events carry no shapes, so
programs that share a name across shapes (``jit(many)`` per context window)
count too: it is an upper bound on builds that bought nothing, read together
with the names in the ring.
"""

from __future__ import annotations

import contextlib
import threading
import time
from collections import deque

from jax import monitoring

STAGES = ("trace", "lower", "backend")
THREAD_CLASSES = ("loop", "prewarm", "other")
RING_CAPACITY = 512

_STAGE_OF = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"

_lock = threading.Lock()
_tls = threading.local()
# flat cumulative counters, so that a reader's baseline is one dict copy:
# "programs", "cache_hits", "repeat_builds", "s.<stage>", and the same under
# "<thread class>." for each class
_counters: dict[str, float] = {}
_ring: deque[dict] = deque(maxlen=RING_CAPACITY)
_seen: set[tuple[str, bool]] = set()


def set_thread_class(name: str) -> None:
    """Called by a thread about itself: the step loops say "loop", the
    window prewarm says "prewarm"; a thread that says nothing is "other"."""
    _tls.cls = name


@contextlib.contextmanager
def thread_class(name: str):
    """This thread's builds count under `name` inside the block (the loop's
    one call of a program the prewarm thread built is prewarm work)."""
    before = getattr(_tls, "cls", "other")
    _tls.cls = name
    try:
        yield
    finally:
        _tls.cls = before


def enter_step(seq: int) -> None:
    """Builds on this thread belong to step `seq` until leave_step()."""
    _tls.step = (seq, [])


def leave_step() -> list[str]:
    """Names of the programs built on this thread since enter_step()."""
    step = getattr(_tls, "step", None)
    _tls.step = None
    return step[1] if step else []


def _bump(key: str, cls: str, by: float = 1) -> None:
    _counters[key] = _counters.get(key, 0) + by
    key = f"{cls}.{key}"
    _counters[key] = _counters.get(key, 0) + by


def _pending() -> dict:
    """What this thread has traced and lowered since its last build, and
    whether the persistent cache answered."""
    pending = getattr(_tls, "pending", None)
    if pending is None:
        pending = _tls.pending = {"trace": 0.0, "lower": 0.0, "hit": False}
    return pending


def _on_duration(event: str, seconds: float, **kw) -> None:
    stage = _STAGE_OF.get(event)
    if stage is None:
        return
    cls = getattr(_tls, "cls", "other")
    pending = _pending()
    if stage != "backend":
        pending[stage] += seconds
        with _lock:
            _bump(f"s.{stage}", cls, seconds)
        return
    fun_name = str(kw.get("fun_name"))
    hit = pending["hit"]
    step = getattr(_tls, "step", None)
    if step:
        step[1].append(fun_name)
    with _lock:
        _bump("s.backend", cls, seconds)
        _bump("programs", cls)
        if hit:
            _bump("cache_hits", cls)
        if (fun_name, hit) in _seen:
            _bump("repeat_builds", cls)
        _seen.add((fun_name, hit))
        _ring.append({
            "n": int(_counters["programs"]), "ts": time.time(),
            "fun_name": fun_name, "thread": cls,
            "trace_s": round(pending["trace"], 6),
            "lower_s": round(pending["lower"], 6),
            "backend_s": round(seconds, 6), "cache_hit": hit,
            "step_seq": step[0] if step else None,
        })
    pending["trace"] = pending["lower"] = 0.0
    pending["hit"] = False


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT:
        _pending()["hit"] = True


monitoring.register_event_duration_secs_listener(_on_duration)
monitoring.register_event_listener(_on_event)


def counters() -> dict[str, float]:
    """The cumulative counters as they stand: a reader's baseline."""
    with _lock:
        return dict(_counters)


def _block(c: dict[str, float], prefix: str = "") -> dict:
    return {
        "programs_total": int(c.get(prefix + "programs", 0)),
        "cache_hits_total": int(c.get(prefix + "cache_hits", 0)),
        "repeat_builds_total": int(c.get(prefix + "repeat_builds", 0)),
        "seconds_total": {s: round(c.get(f"{prefix}s.{s}", 0.0), 6)
                          for s in STAGES},
    }


def summary(since: dict[str, float] | None = None) -> dict:
    """Totals since the baseline `since` (a counters() copy; None: since
    the import), whole and by the class of the thread that built."""
    now = counters()
    if since:
        now = {k: v - since.get(k, 0) for k, v in now.items()}
    out = _block(now)
    out["by_thread"] = {cls: _block(now, f"{cls}.") for cls in THREAD_CLASSES}
    return out


def recent(limit: int = 64, since: dict[str, float] | None = None
           ) -> list[dict]:
    """The last `limit` builds, newest first, of those after the baseline."""
    if limit <= 0:
        return []
    first = int(since.get("programs", 0)) if since else 0
    with _lock:
        builds = [dict(b) for b in _ring if b["n"] > first]
    return builds[-limit:][::-1]
