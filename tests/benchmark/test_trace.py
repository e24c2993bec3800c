"""The trace reduction on a hand-built stand-in for `ProfileData` (planes →
lines → events), against numbers worked out by hand.

The device line, in milliseconds (one device plane):

    0        10        20        30        40        50        60
    |--fusion.1--|          |-------while.3--------|     |-pfd.7-|
    0          10          20  [fusion.2 22..30]   40   45     55
                                [pfd.8   30..38]

busy = 10 + 20 + 10 = 40 ms of a 60 ms window -> idle 1/3.
self times: fusion (two shapes) 10 and 8, while 20 - 8 - 8 = 4,
paged_flash_decode 8 + 10 = 18.  Gaps: 10..20 (10 ms), 40..45 (5 ms).
"""

import dataclasses

import pytest

from benchmark import trace


@dataclasses.dataclass
class Ev:
    name: str
    start_ns: float
    duration_ns: float
    stats: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Line:
    name: str
    events: list


@dataclasses.dataclass
class Plane:
    name: str
    lines: list


@dataclasses.dataclass
class Profile:
    planes: list


MS = 1e6


def profile():
    ops = Line("XLA Ops", [
        Ev("%fusion.1 = bf16[32,14336]{1,0:T(8,128)} fusion(bf16[32,4096]{1,0} %p)", 0, 10 * MS),
        Ev("while.3", 20 * MS, 20 * MS),
        Ev("%fusion.2 = f32[32]{0:T(128)} fusion(f32[32,4096]{1,0} %q)", 22 * MS, 8 * MS),
        Ev("paged_flash_decode.8", 30 * MS, 8 * MS),
        Ev("paged_flash_decode.7", 45 * MS, 10 * MS),
    ])
    modules = Line("XLA Modules", [
        Ev("jit_many(1234567)", 20 * MS, 20 * MS),
        Ev("jit_many(1234567)", 45 * MS, 10 * MS),
        Ev("jit_prefill_into_pages(99)", 0, 10 * MS),
    ])
    host = Line("python", [Ev("bench.clock_sync", 5 * MS, 1 * MS),
                           Ev("other", 1 * MS, 1 * MS)])
    return Profile([Plane("/host:CPU", [host]),
                    Plane("/device:TPU:0", [ops, modules, Line("Steps", [])]),
                    Plane("/host:metadata", [])])


# stepstats records on the wall clock; the trace's zero is wall 1000.0
STEPS = [
    # ends at 1000.021: a prefill whose emit phase covers trace 10..20 ms
    {"ts": 1000.021, "kind": "prefill", "total_s": 0.021,
     "phases_s": {"plan": 0.001, "dispatch": 0.002, "compute": 0.007,
                  "emit": 0.011}},
    # ends at 1000.060: a decode step; its dispatch phase covers 40..45 ms
    {"ts": 1000.060, "kind": "decode", "total_s": 0.030,
     "phases_s": {"plan": 0.010, "dispatch": 0.006, "compute": 0.010,
                  "fetch": 0.002, "emit": 0.002}},
]


def test_busy_idle_ops_modules_and_gaps_by_hand():
    out = trace.reduce(profile(), window_s=0.060, steps=STEPS,
                       clock_offset_s=1000.0)
    assert out["device_planes"] == 1
    assert out["busy_s"] == pytest.approx(0.040)
    assert 1 - out["busy_s"] / out["window_s"] == pytest.approx(1 / 3)
    ops = out["ops"]
    assert ops["fusion_bf16_32_14336_"]["time_s"] == pytest.approx(0.010)
    assert ops["fusion_f32_32_"]["time_s"] == pytest.approx(0.008)
    assert ops["while"]["time_s"] == pytest.approx(0.004)  # self time only
    assert ops["paged_flash_decode"] == {"time_s": pytest.approx(0.018), "count": 2}
    assert sum(v["time_s"] for v in ops.values()) == pytest.approx(out["busy_s"])
    assert out["breakdown"]["device_ops"][0] == ["paged_flash_decode", pytest.approx(0.018)]
    mods = out["modules"]
    assert mods["jit_many"]["count"] == 2
    assert mods["jit_many"]["median_s"] == pytest.approx(0.015)
    assert mods["jit_many"]["time_s"] == pytest.approx(0.030)
    assert mods["jit_prefill_into_pages"]["time_s"] == pytest.approx(0.010)
    gaps = out["breakdown"]["idle_gaps"]
    assert gaps == [["prefill.emit", pytest.approx(0.010)],
                    ["decode.dispatch", pytest.approx(0.005)]]


def test_gaps_stay_unnamed_without_a_clock_anchor():
    out = trace.reduce(profile(), window_s=0.060, steps=STEPS, clock_offset_s=None)
    assert [g[0] for g in out["breakdown"]["idle_gaps"]] == ["unattributed"] * 2


def test_the_clock_anchor_is_the_named_host_event():
    assert trace.find_host_event(profile(), "bench.clock_sync") == pytest.approx(0.005)
    assert trace.find_host_event(profile(), "absent") is None


def test_a_trace_with_no_device_plane_reports_no_busy_time():
    out = trace.reduce(Profile([Plane("/host:CPU", [])]), window_s=1.0)
    assert out["busy_s"] is None and out["device_planes"] == 0


@pytest.mark.parametrize("t,want", [
    (1000.0005, "prefill.plan"), (1000.002, "prefill.dispatch"),
    (1000.005, "prefill.compute"), (1000.015, "prefill.emit"),
    (1000.025, "between_steps"), (1000.035, "decode.plan"),
    (1000.059, "decode.emit"), (1000.5, "after_last_step"),
])
def test_host_phase_at(t, want):
    assert trace.host_phase_at(t, STEPS) == want


@pytest.mark.parametrize("name,want", [
    ("paged_flash_decode", "paged_flash_decode"),
    ("copy.12", "copy"),
    # a TPU trace names an event by its whole HLO instruction
    ("%paged_flash_decode.176 = bf16[32,8,4,128]{3,2,1,0:T(4,128)(2,1)S(1)} custom-call(s32[32,16]{1,0} %x)",
     "paged_flash_decode_bf16_32_8_4_128_"),
    ("%fusion.1409.remat6 = (bf16[1,4096,4096]{1,2,0}, bf16[1,4096,4096]{1,2,0}) fusion(bf16[16,4096,4096]{1,2,0} %g)",
     "fusion_bf16_1_4096_4096_"),
    ("%slice_bitcast_fusion.78 = bf16[400,128,8,128]{3,2,1,0:T(8,128)(2,1)} fusion(bf16[16,400,128,8,128]{4,3,2,1,0} %f)",
     "slice_bitcast_fusion_bf16_400_128_8_128_"),
    ("%while.7 = (s32[]{:T(128)}, s32[32]{0}) while((s32[]{:T(128)}) %t), condition=%c, body=%b",
     "while_s32__"),
])
def test_op_label(name, want):
    assert trace.op_label(name) == want


def test_merge_and_self_times():
    assert trace.merge([(0, 2), (1, 3), (5, 6), (6, 6)]) == [(0, 3), (5, 6)]
    evs = [(0.0, 10.0, "outer"), (1.0, 4.0, "a"), (2.0, 3.0, "b"), (5.0, 6.0, "c")]
    got = dict(trace.self_times(evs))
    assert got == {"outer": 6.0, "a": 2.0, "b": 1.0, "c": 1.0}


def test_structure_lists_planes_lines_and_first_events():
    s = trace.structure(profile(), per_line=1)
    dev = next(p for p in s if p["plane"] == "/device:TPU:0")
    assert [ln["line"] for ln in dev["lines"]] == ["XLA Ops", "XLA Modules", "Steps"]
    assert dev["lines"][0]["events"] == 5 and len(dev["lines"][0]["first"]) == 1
