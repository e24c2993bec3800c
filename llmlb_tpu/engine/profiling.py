"""On-demand jax.profiler capture for a live engine.

The reference stack has no profiler surface (SURVEY §5: "no flamegraph/pprof
tooling"); on TPU this is how an operator answers "where do my step
milliseconds go" below the step-phase breakdown's resolution — XLA ops,
Pallas kernels, H2D/D2H transfers, per-core timelines, all without
restarting the serving process.

One ProfileManager per engine process guards the GLOBAL jax tracer (two
concurrent start_trace calls would corrupt each other): start → bounded
auto-stop timer → downloadable zip artifact. Captures are strictly opt-in
per request — nothing records until POST /api/profile starts a capture, and
every capture self-terminates at its bounded duration even if the client
never calls stop.

Gating: the engine port is unauthenticated by design (it sits behind the
gateway), so capture access is controlled by LLMLB_PROFILE_TOKEN — when
set, start/stop/artifact require `Authorization: Bearer <token>`. Unset
(dev/bench hosts), the endpoint is open like the rest of the engine API.
"""

from __future__ import annotations

import logging
import os
import shutil
import tempfile
import threading
import time
import uuid
import zipfile

log = logging.getLogger("llmlb_tpu.engine.profiling")

MAX_CAPTURE_S = 60.0  # the global tracer buffers in RAM; bound it hard
MAX_KEPT_CAPTURES = 4  # older trace dirs are deleted as new ones land


class ProfileError(RuntimeError):
    def __init__(self, status: int, message: str):
        super().__init__(message)
        self.status = status


class ProfileManager:
    """Start/stop lifecycle around jax.profiler's global tracer plus a
    small ledger of completed captures for artifact download."""

    def __init__(self, trace_root: str | None = None):
        self._lock = threading.Lock()
        self._active: dict | None = None  # {id, dir, started_at, seconds}
        self._timer: threading.Timer | None = None
        self._captures: list[dict] = []  # completed, newest last
        self._root_override = trace_root
        # Set while no capture runs or is being written, cleared from a
        # capture's start until its files are on disk and it is in the
        # ledger: wait_idle() parks on it instead of polling status() —
        # set/clear only ever happen with _lock held, so waiters can't miss
        # an edge.
        self._idle = threading.Event()
        self._idle.set()

    # ---------------------------------------------------------------- control

    def start(self, seconds: float) -> dict:
        """Begin a capture with a bounded auto-stop. Raises ProfileError 409
        if one is already running."""
        import jax

        seconds = min(MAX_CAPTURE_S, max(0.1, float(seconds)))
        # Traces always land under a server-controlled root (resolved per
        # capture so LLMLB_TRACE_DIR set after startup is honored) — the
        # engine port is unauthenticated, so a client-supplied path would
        # be an arbitrary directory-write primitive.
        root = (self._root_override or os.environ.get("LLMLB_TRACE_DIR")
                or tempfile.gettempdir())
        with self._lock:
            if self._active is not None:
                raise ProfileError(409, "a profile capture is already running")
            if not self._idle.is_set():
                # stopped, but stop_trace is still writing the files: the
                # tracer is global and not ours again until it returns
                raise ProfileError(409, "the last profile capture is still "
                                        "being written")
            # dir creation inside the lock, AFTER the busy check: a polling
            # client hammering start while a capture runs must not litter
            # the trace root with empty dirs the eviction never sees
            os.makedirs(root, exist_ok=True)
            out_dir = tempfile.mkdtemp(prefix="llmlb-trace-", dir=root)
            # start inside the lock: the tracer is global, and a concurrent
            # start would race the `_active` claim
            try:
                jax.profiler.start_trace(out_dir)
            except Exception as e:
                shutil.rmtree(out_dir, ignore_errors=True)
                raise ProfileError(500, f"profiler failed to start: {e}")
            capture = {
                "capture_id": uuid.uuid4().hex[:12],
                "trace_dir": out_dir,
                "started_at": time.time(),
                "seconds_requested": seconds,
            }
            self._active = capture
            self._idle.clear()
            self._timer = threading.Timer(seconds, self._auto_stop,
                                          args=(capture["capture_id"],))
            self._timer.daemon = True
            self._timer.start()
        log.info("profile capture %s started (%.1fs max) -> %s",
                 capture["capture_id"], seconds, out_dir)
        return {"capture_id": capture["capture_id"], "seconds": seconds,
                "trace_dir": out_dir}

    def stop(self) -> dict:
        """Stop the running capture early. Raises ProfileError 409 when
        nothing is recording."""
        done = self._finish(expected_id=None)
        if done is None:
            raise ProfileError(409, "no profile capture is running")
        return done

    def _auto_stop(self, capture_id: str) -> None:
        try:
            self._finish(expected_id=capture_id)
        except Exception:  # pragma: no cover - defensive: timer thread
            log.exception("profile auto-stop failed")

    def _finish(self, expected_id: str | None) -> dict | None:
        import jax

        # Claim the capture under the lock, but run stop_trace (which
        # SERIALIZES the whole trace — seconds for a long TPU capture) and
        # the size walk OUTSIDE it, so status()/start() callers — and
        # through them the server event loop — never block behind the
        # trace write. The claim (active -> None) makes the stop exclusive:
        # a concurrent stop sees None and 409s.
        with self._lock:
            active = self._active
            if active is None:
                return None
            if expected_id is not None and \
                    active["capture_id"] != expected_id:
                return None  # an explicit stop already closed this capture
            if self._timer is not None:
                self._timer.cancel()
                self._timer = None
            self._active = None
        try:
            jax.profiler.stop_trace()
        except Exception as e:
            log.exception("profiler stop failed")
            active["error"] = f"stop failed: {e}"
        active["stopped_at"] = time.time()
        active["duration_s"] = round(
            active["stopped_at"] - active["started_at"], 3
        )
        active["bytes"] = _dir_bytes(active["trace_dir"])
        with self._lock:
            self._captures.append(active)
            # bound disk: drop the oldest trace dirs beyond the keep window
            evicted = []
            while len(self._captures) > MAX_KEPT_CAPTURES:
                evicted.append(self._captures.pop(0))
            # only now is the capture over for a waiter: the trace files
            # exist and status()/artifact() know the capture
            self._idle.set()
        for stale in evicted:
            shutil.rmtree(stale["trace_dir"], ignore_errors=True)
            zip_path = stale["trace_dir"].rstrip("/") + ".zip"
            try:
                os.unlink(zip_path)
            except OSError:
                pass
        log.info("profile capture %s stopped after %.2fs (%d bytes)",
                 active["capture_id"], active["duration_s"], active["bytes"])
        return self._public(active)

    # ---------------------------------------------------------------- reading

    @staticmethod
    def _public(capture: dict) -> dict:
        out = dict(capture)
        out["download"] = f"/api/profile/{capture['capture_id']}"
        return out

    def wait_idle(self, timeout_s: float) -> bool:
        """Block (a worker thread — never the event loop) until the running
        capture finishes, waking on the stop itself rather than polling
        status(). True when idle; False when the timeout passed first."""
        return self._idle.wait(timeout_s)

    def status(self) -> dict:
        with self._lock:
            active = dict(self._active) if self._active else None
            captures = [self._public(c) for c in reversed(self._captures)]
        if active is not None:
            active["elapsed_s"] = round(time.time() - active["started_at"], 2)
        return {"recording": active is not None, "active": active,
                "captures": captures}

    def artifact(self, capture_id: str) -> tuple[str, str]:
        """(zip path, download filename) of a completed capture's trace
        directory — the downloadable artifact for `tensorboard --logdir` /
        xprof. The zip is built ON DISK beside the trace dir (TPU captures
        run to hundreds of MB; buffering them in RAM on the serving host is
        not acceptable) and cached for repeat downloads. Call from a worker
        thread — deflate of a large trace takes seconds."""
        with self._lock:
            capture = next((c for c in self._captures
                            if c["capture_id"] == capture_id), None)
        if capture is None:
            raise ProfileError(404, f"no completed capture {capture_id!r}")
        root = capture["trace_dir"].rstrip("/")
        zip_path = root + ".zip"
        filename = f"llmlb-trace-{capture_id}.zip"
        if os.path.isfile(zip_path):
            return zip_path, filename
        # build to a temp name then rename: a concurrent download never
        # sees a half-written zip
        tmp_path = zip_path + ".tmp"
        try:
            names = 0
            with zipfile.ZipFile(tmp_path, "w", zipfile.ZIP_DEFLATED) as zf:
                for dirpath, _dirs, files in os.walk(root):
                    for name in files:
                        full = os.path.join(dirpath, name)
                        zf.write(full, os.path.relpath(full, root))
                        names += 1
            if names == 0:
                raise ProfileError(500, "capture produced no trace files")
            os.replace(tmp_path, zip_path)
        except OSError as e:
            # the eviction in _finish may rmtree this capture's dir while
            # we walk it — report it gone, not a raw 500 traceback
            try:
                os.unlink(tmp_path)
            except OSError:
                pass
            raise ProfileError(
                404, f"capture {capture_id!r} no longer on disk: {e}"
            )
        return zip_path, filename


def _dir_bytes(root: str) -> int:
    total = 0
    for dirpath, _dirs, files in os.walk(root):
        for name in files:
            try:
                total += os.path.getsize(os.path.join(dirpath, name))
            except OSError:
                pass
    return total
