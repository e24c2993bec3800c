"""A token's way out of the engine, counted where the work happens.

Between the scheduler's `_emit` and the engine's socket a token passes three
stages, and each has a counter here (docs/tracing.md "A token's way out"):

  the event queue and the hop — `Request.events` is an `EventQueue`: `put`
      stamps the event. `Engine.stream` takes each event through one
      `run_in_executor` call of the queue's C-level `get` (`taker`: the
      bridge thread runs no Python of ours) and, resumed on the event loop,
      reads how long ago the event was put: its wait in the queue and the
      hop back to the loop together, which is how long the token waited for
      the service's consumer. Every other consumer and every test of
      `("tokens", [ids])` / `("done", reason)` calls `get` and sees the
      events as they were put. An event carries what one fetch brought
      the request (a burst's k tokens of a row), so these are counts and
      costs per event, and `tokens_total` beside them says how many
      tokens shared one.
  the frame — from the consumer's resumption with an event to the
      generator's resumption after the delta's `yield`: detokenisation,
      the handler's JSON, `_sse_send` and its `await resp.write`.
  the write — a `resp.write` that found the connection paused (the reader
      downstream is slower than the engine writes: TCP back-pressure) is
      timed and counted as a wait; the others cost one attribute read.

`StreamStats` is the cumulative block `/api/health .metrics.stream`
(`llmlb_engine_stream_*` in `/metrics`); window differences give every
reading. All of it is written by ONE thread, the HTTP event loop, so it
takes no lock; the queue's `n_put` is written by its one producer. Stamps
are `stepstats._now`, three clock reads an event in all (the put, the
resumption, the frame's end); a whole stream's two durations are on the
same clock, as `Request.submitted_at` and `finished_at` are.

The LAST stage of a request's way in is counted here too (docs/tracing.md
"A request's way in"): `first_frames_total` and `first_frame_seconds_total`
hold, for the first delta a stream yields, the time from its event's put to
the generator's resumption after the yield — the frame's own end stamp, no
further read.
"""

from __future__ import annotations

import contextlib
import functools
import queue

from jax.profiler import TraceAnnotation

from llmlb_tpu.engine import stepstats


class EventQueue(queue.SimpleQueue):
    """`Request.events`: a SimpleQueue whose items carry the instant they
    were put. One producer at a time (the step loop) and one consumer."""

    __slots__ = ("n_put",)

    def __init__(self):
        super().__init__()
        self.n_put = 0  # events ever put

    def put(self, item, block: bool = True, timeout=None) -> None:
        self.n_put += 1
        super().put((stepstats._now(), item))

    def put_nowait(self, item) -> None:
        self.put(item)

    def get(self, block: bool = True, timeout=None):
        return super().get(block, timeout)[1]

    def get_nowait(self):
        return super().get(False)[1]

    def taker(self):
        """A blocking C-level get for an executor thread: it returns
        `(stamp, item)` and runs no Python on that thread."""
        return functools.partial(queue.SimpleQueue.get, self)


class StreamStats:
    """The cumulative counters of the stream path (module docstring)."""

    def __init__(self):
        self.events_total = 0             # events the consumers took
        self.tokens_total = 0             # tokens those events carried
        self.event_wait_seconds_total = 0.0   # put -> resumed on the loop
        self.event_backlog_max = 0
        self.frames_total = 0
        self.frame_seconds_total = 0.0
        self.first_frames_total = 0       # streams whose first delta is out
        self.first_frame_seconds_total = 0.0  # its event's put -> written
        self.write_waits_total = 0        # writes that found the reader behind
        self.write_wait_seconds_total = 0.0
        self.streams_finished_total = 0
        self.stream_seconds_total = 0.0   # submitted -> last frame written
        self.made_seconds_total = 0.0     # submitted -> the scheduler's done
        self.events_unread_total = 0      # left queued by a consumer that quit
        self._closed_put = 0
        self._live: set[EventQueue] = set()

    # ---- the consumer's side (Engine.stream / adopt_stream, event loop)

    def open(self, q: EventQueue) -> None:
        self._live.add(q)

    def close(self, q: EventQueue) -> None:
        self._live.discard(q)
        self._closed_put += q.n_put
        self.events_unread_total += q.qsize()

    def got(self, stamp: float, q: EventQueue, tokens: int) -> float:
        """The consumer resumed with an event put at `stamp`; the instant
        is returned (the frame's start)."""
        now = stepstats._now()
        self.events_total += 1
        self.tokens_total += tokens
        self.event_wait_seconds_total += now - stamp
        backlog = q.qsize()
        if backlog > self.event_backlog_max:
            self.event_backlog_max = backlog
        return now

    def frame(self, t0: float, first_put: float | None = None) -> None:
        """The generator resumed after the `yield` of the delta whose event
        arrived at `t0`. `first_put`: it is the stream's first delta, and
        its event was put then."""
        now = stepstats._now()
        self.frames_total += 1
        self.frame_seconds_total += now - t0
        if first_put is not None:
            self.first_frames_total += 1
            self.first_frame_seconds_total += now - first_put

    def finished(self, request) -> None:
        """The last frame of a stream the scheduler finished is written."""
        if request.finished_at is None:
            return
        self.streams_finished_total += 1
        self.stream_seconds_total += stepstats._now() - request.submitted_at
        self.made_seconds_total += request.finished_at - request.submitted_at

    # ---- the writer's side (server._sse_send, event loop)

    def write_waited(self, t0: float) -> None:
        """A write begun at `t0` on a paused connection has returned."""
        self.write_waits_total += 1
        self.write_wait_seconds_total += stepstats._now() - t0

    # ---- reading

    def snapshot(self) -> dict:
        live = list(self._live)
        out = {k: (round(v, 6) if isinstance(v, float) else v)
               for k, v in vars(self).items() if not k.startswith("_")}
        # conservation: put = taken + queued + unread, but for the events in
        # the hop at this instant (at most one a live stream)
        out["events_put_total"] = self._closed_put + sum(q.n_put for q in live)
        out["events_queued"] = sum(q.qsize() for q in live)
        out["streams_live"] = len(live)
        return out


_NO_FRAME = contextlib.nullcontext()
_capturing = TraceAnnotation.is_enabled


def frame_annotation():
    """The consumer's get -> frame-written interval on the host plane of a
    capture. Outside one it is nothing at all: an event a token is no place
    for an object a time (one C call asks whether a capture runs)."""
    return TraceAnnotation("llmlb.stream.frame") if _capturing() else _NO_FRAME


def first_token_annotation(request_id: str, fetch_seq: int):
    """The instant a request's first token reached the host
    (scheduler._first_token), on the host plane of a capture: it lies in
    the `llmlb.step` that emits it and names the step whose fetch brought
    it (`fetch_seq`: the same step in today's order, the one before where
    the emit runs behind the next dispatch). Nothing outside a capture."""
    if not _capturing():
        return _NO_FRAME
    return TraceAnnotation("llmlb.first_token", request_id=request_id,
                           fetch_seq=fetch_seq)
