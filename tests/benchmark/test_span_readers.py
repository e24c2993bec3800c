"""The nine readers of the step loop's measured timeline (PR 24), each worked
out by hand on a built run, and each reporting nothing on the records of a
program that serves no spans, no loop buckets and no program ledger."""

import pytest

from benchmark import manifest as mf
from tests.benchmark.test_readers import (LOOP_END, LOOP_START, ledger,
                                          step as span_fields)

DECODE = [("host_sync", 0.01), ("dispatch", 0.01), ("compute", 0.20),
          ("fetch", 0.02), ("emit", 0.01)]  # 0.25 s


def collected():
    steps = [
        span_fields({"kind": "decode"}, 100.00, DECODE, {"other_s": 0.002}, 4),
        # a one-shot prefill: its activation is a span of its own
        span_fields({"kind": "prefill"}, 100.26, [
            ("dispatch", 0.01), ("compute", 0.06), ("emit", 0.005),
            ("activate", 0.025)], {"admit_s": 0.008, "record_s": 0.002}, 1),
        span_fields({"kind": "decode"}, 100.37, DECODE, {"record_s": 0.01}, 5),
        # 2.38 s later, 2.3 s of it asleep with nothing to do: no stall
        span_fields({"kind": "decode"}, 103.00, DECODE,
                    {"idle_s": 2.3, "admit_s": 0.05, "other_s": 0.03}, 2),
        # a chunk of a long prompt: no activation yet
        span_fields({"kind": "prefill"}, 103.26, [
            ("dispatch", 0.02), ("compute", 0.12)], {"record_s": 0.01}, 1),
        # a second of the loop's time that nothing names, then a decode
        span_fields({"kind": "decode"}, 104.40, DECODE, {"other_s": 1.0}, 3),
    ]
    return {
        "steps": steps,
        "health_start": {"metrics": {"loop_seconds_total": LOOP_START,
                                     "compile": ledger(240)}},
        "health_end": {"metrics": {"loop_seconds_total": LOOP_END,
                                   "compile": ledger(243)}},
    }


def parents():
    """The same run as a commit before PR 24 records it."""
    c = collected()
    keep = ("kind", "active_slots")
    c["steps"] = [{k: r[k] for k in keep} for r in c["steps"]]
    c["health_start"] = {"metrics": {"tokens_total": 1}}
    c["health_end"] = {"metrics": {"tokens_total": 9}}
    return c


def layer(name, c):
    return mf.load_module("layer_metrics", name).read(c)


BY_HAND = [
    # the one activate span over the six steps' wall time
    ("sched.activate_share", 100 * 0.025 / (4 * 0.25 + 0.10 + 0.14)),
    # window of 52 s, 10 s of it idle: (1 + 0 + .5 + .5) of the busy 42 s
    ("sched.loop_overhead_share", 100 * 2.0 / 42.0),
    ("sched.loop_unattributed_share", 100 * 0.5 / 42.0),
    # decode steps end at 100.25, 100.62, 103.25 and 104.65: stretches of
    # 0.37, 2.63 less 2.3 asleep, and 1.40
    ("sched.longest_stall_s", 1.40),
    ("setup.programs_built", 240.0),
    ("setup.trace_lower_s", 35.0),
    ("setup.backend_compile_s", 12.5),
    ("setup.prewarm_build_s", 26.0),
    ("engine.programs_built_in_window", 3.0),
]


@pytest.mark.parametrize("name,want", BY_HAND)
def test_span_readers_by_hand(name, want):
    assert layer(name, collected()) == pytest.approx(want, rel=1e-9)


@pytest.mark.parametrize("name", [name for name, _ in BY_HAND])
def test_a_span_reader_reports_nothing_on_the_parents_records(name):
    assert layer(name, parents()) is None
    assert layer(name, {"steps": [], "health_start": None,
                        "health_end": None}) is None


def test_the_loop_buckets_are_summed_over_the_loops():
    c = collected()
    c["health_end"]["metrics"]["loop_seconds_total"] = {
        **LOOP_END, "decode": {"step": 8.0, "other": 2.0}}
    # a loop that began inside the window counts from nothing: 42 + 10 busy
    assert layer("sched.loop_unattributed_share", c) == pytest.approx(
        100 * 2.5 / 52.0)


def test_a_stretch_is_counted_only_between_decode_steps_with_work():
    c = collected()
    assert layer("sched.longest_stall_s", {**c, "steps": c["steps"][:2]}) is None
    # the sleep before a prefill step is the loop's idle time all the same
    c["steps"][4]["since_prev"]["idle_s"] = 0.9
    assert layer("sched.longest_stall_s", c) == pytest.approx(0.5)


def test_every_new_reader_is_in_the_manifest_for_its_cells():
    """Eight of the nine list no cells (PR 26), so a cell added later reads
    the host timeline and the set-up ledger without an edit to an entry; the
    stall detector stays with the open loop, which alone reports the metric
    it moves."""
    manifest = mf.load()
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name, _ in BY_HAND:
        if name == "sched.longest_stall_s":
            assert by_name[name]["workloads"] == ["mistral-7b-l16.chat-paced"]
        else:
            assert "workloads" not in by_name[name], name
