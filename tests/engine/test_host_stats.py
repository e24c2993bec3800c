"""What the process spends on the host (hoststats.py) and what the step
loop's records say of it (engine/stepstats.py): CPU seconds by thread class,
the collector's clock, and `host_cpu_s` / `gap_cpu_s` / `gc_s` on a step
record with patched clocks and a forced collection."""

import gc
import threading
import time

import pytest

from llmlb_tpu import hoststats
from llmlb_tpu.engine import stepstats
from llmlb_tpu.engine.metrics import THREAD_CLASSES, EngineMetrics
from llmlb_tpu.engine.stepstats import LoopClock, StepRecorder


def _burn(seconds: float) -> None:
    end = time.thread_time() + seconds
    while time.thread_time() < end:
        pass


# ------------------------------------------------------ CPU by thread class


def test_cpu_seconds_by_thread_name_class():
    # what threads of earlier tests in this process hold already (an engine
    # some test left running keeps its bridge threads): read against it
    base = hoststats.cpu_seconds(THREAD_CLASSES, current="http_loop")
    stop = threading.Event()

    def work():
        _burn(0.05)
        stop.wait(5)

    threads = [threading.Thread(target=work, name=name, daemon=True)
               for name in ("engine-step-loop", "engine-events_0",
                            "engine-events_1", "somebody-else")]
    for t in threads:
        t.start()
    try:
        time.sleep(0.3)
        me0 = time.thread_time()
        out = hoststats.cpu_seconds(THREAD_CLASSES, current="http_loop")
        assert set(out) == {"process", "step_loop", "http_loop",
                            "event_bridge", "prewarm", "other"}
        assert out["step_loop"] - base["step_loop"] >= 0.045
        # two threads of the pool
        assert out["event_bridge"] - base["event_bridge"] >= 0.09
        assert out["prewarm"] == base["prewarm"]  # no such thread started
        # the calling thread is the class it says it is
        assert out["http_loop"] == pytest.approx(me0, abs=0.05)
        # `other` holds the thread no class names, and is never negative
        assert out["other"] >= 0.045
        named = sum(v for k, v in out.items() if k not in ("process", "other"))
        assert named + out["other"] == pytest.approx(out["process"], abs=1e-3)
        assert named <= out["process"] + 1e-3
    finally:
        stop.set()
        for t in threads:
            t.join()
    # the threads have ended: their seconds are no class's any more, nothing
    # raises, and `other` keeps them (process time does not fall)
    after = hoststats.cpu_seconds(THREAD_CLASSES, current="http_loop")
    assert after["step_loop"] <= base["step_loop"] + 0.02
    assert after["event_bridge"] <= base["event_bridge"] + 0.02
    assert after["other"] >= out["other"] and after["process"] >= out["process"]


def test_a_thread_that_dies_under_the_reader_does_not_raise(monkeypatch):
    """A thread listed alive whose clock cannot be read any more (it ended
    between the listing and the read) is skipped."""
    t = threading.Thread(target=lambda: None, name="engine-events_9")
    t.start()
    t.join()
    monkeypatch.setattr(threading, "enumerate",
                        lambda: [t, threading.current_thread()])
    out = hoststats.cpu_seconds(THREAD_CLASSES)  # t.is_alive() is False
    assert out["event_bridge"] == 0.0

    def gone(_ident):
        raise OSError("no such thread")

    monkeypatch.setattr(t, "is_alive", lambda: True)
    monkeypatch.setattr(time, "pthread_getcpuclockid", gone)
    out = hoststats.cpu_seconds(THREAD_CLASSES, current="http_loop")
    assert out["event_bridge"] == 0.0 and out["http_loop"] == 0.0
    assert out["other"] == pytest.approx(out["process"])


def test_other_is_never_negative(monkeypatch):
    # thread clocks can run a tick ahead of the process clock's reading
    monkeypatch.setattr(time, "process_time", lambda: 0.0)
    out = hoststats.cpu_seconds({}, current="loop")
    assert out["other"] == 0.0 and out["loop"] >= 0.0


# ------------------------------------------------------------ the collector


def test_gc_clock_counts_collections_by_generation():
    stamps = iter([1.0, 1.5, 2.0, 2.25])
    clock = hoststats.GcClock(now=lambda: next(stamps))
    clock("stop", {"generation": 0})  # a stop with no start: ignored
    clock("start", {"generation": 2})
    clock("stop", {"generation": 2, "collected": 0})
    clock("start", {"generation": 0})
    clock("stop", {"generation": 0})
    assert clock.snapshot() == {
        "collections_total": {"0": 1, "1": 0, "2": 1},
        "seconds_total": 0.75}


def test_the_engine_listens_to_the_collector_once():
    a, b = EngineMetrics(), EngineMetrics()
    assert a.gc is b.gc is hoststats.GC
    assert gc.callbacks.count(hoststats.GC) == 1
    before = a.gc.snapshot()
    gc.collect()
    after = a.host_info()["gc"]
    assert after["collections_total"]["2"] == \
        before["collections_total"]["2"] + 1
    assert after["seconds_total"] > before["seconds_total"]
    assert set(a.summary()) >= {"stream", "gc", "cpu_seconds_total"}
    text = a.render(queue_depth=0, active_slots=0, num_slots=1)
    assert 'llmlb_engine_gc_collections_total{generation="2"}' in text
    assert 'llmlb_engine_cpu_seconds_total{class="step_loop"}' in text


# ----------------------------------------------- the step record's CPU, gc


def _clocks(monkeypatch, wall, cpu):
    w, c = iter(wall), iter(cpu)
    monkeypatch.setattr(stepstats, "_now", lambda: next(w))
    monkeypatch.setattr(stepstats, "_cpu", lambda: next(c))


def test_host_cpu_and_gap_cpu_on_a_step_record(monkeypatch):
    hoststats.watch_gc()
    _clocks(monkeypatch,
            wall=[5.0,     # the recorder's wall anchor
                  10.0,    # the clock is made
                  10.3,    # begin(host_sync)
                  10.31,   # mark(dispatch)
                  10.32,   # mark(compute)
                  10.52,   # mark(fetch)
                  10.54,   # mark(emit)
                  10.55,   # close
                  10.56,   # resume
                  10.66,   # begin of the next step
                  10.70,   # ... its close
                  10.80],  # a record made by hand
            cpu=[1.000,    # the clock is made
                 1.010,    # begin: 10 ms of CPU in the gap before the step
                 1.025,    # into compute: 15 ms of host work (20 ms of wall)
                 1.026,    # out of compute (what the wait itself burnt)
                 1.030,    # close: 4 ms more (30 ms of wall)
                 1.035,    # the next begin: 5 ms in a gap of 110 ms
                 1.036])   # the next close (never in compute)
    rec = StepRecorder()
    clock = LoopClock(rec, "main")
    step = clock.begin("host_sync")
    for name in ("dispatch", "compute", "fetch", "emit"):
        step.mark(name)
    clock.close(step, "decode")
    rec.observe("decode", step.phases(), span=step)
    clock.resume(step)
    r = rec.snapshot()["records"][0]
    assert r["host_cpu_s"] == pytest.approx(0.019)
    assert r["gap_cpu_s"] == pytest.approx(0.010)
    assert r["gc_s"] == 0.0
    # 50 ms of host wall time in the step (all but `compute`), 19 ms on a
    # CPU: the thread waited 31 ms for the GIL or the kernel's scheduler
    host_wall = sum(dur for name, _at, dur in r["spans"] if name != "compute")
    assert host_wall - r["host_cpu_s"] == pytest.approx(0.031)
    nxt = clock.begin("emit")
    clock.close(nxt, "decode")
    rec.observe("decode", nxt.phases(), span=nxt)
    r2 = rec.snapshot()["records"][0]
    assert r2["gap_cpu_s"] == pytest.approx(0.005)
    assert r2["host_cpu_s"] == pytest.approx(0.001)
    # a record made without a span (the unit tests' path) carries none
    rec.observe("decode", {"compute": 0.01})
    assert "host_cpu_s" not in rec.snapshot()["records"][0]


def test_a_collection_lands_on_the_record_that_was_open():
    """A forced gc.collect() inside a step shows as its gc_s; the next
    record, with no collection, reads 0; one in the gap between two steps
    is the next record's."""
    hoststats.watch_gc()
    gc.collect()  # settle: nothing left that a later collection must free
    rec = StepRecorder()
    clock = LoopClock(rec, "main")

    def one(collect_inside=False):
        step = clock.begin("host_sync")
        step.mark("compute")
        step.mark("emit")
        if collect_inside:
            gc.collect()
        clock.close(step, "decode")
        rec.observe("decode", step.phases(), span=step)
        clock.resume(step)
        return rec.snapshot()["records"][0]

    gc.disable()  # no collection of the interpreter's own in between
    try:
        total0 = hoststats.GC.seconds_total
        hit = one(collect_inside=True)
        assert hit["gc_s"] > 0
        assert hit["gc_s"] == pytest.approx(
            hoststats.GC.seconds_total - total0, abs=1e-6)
        assert hit["gc_s"] <= hit["wall_s"]
        assert one()["gc_s"] == 0.0
        gc.collect()  # between two steps
        assert one()["gc_s"] > 0
        # an abandoned step keeps neither CPU nor a collection from the
        # record after it
        gc.collect()
        clock.begin("host_sync")
        clock.abandon()
        assert one()["gc_s"] > 0
    finally:
        gc.enable()
