"""The warm-up plan is found from the traffic file and the engine's buckets."""

from benchmark import manifest as mf
from benchmark import warmup

ENGINE = {"prefill_buckets": [32, 64, 128, 256, 512], "slot_capacity": 2048,
          "window_buckets": [256, 512, 1024, 2048], "decode_burst": 8}


def plan_for(traffic_name):
    traffic = (traffic_name if isinstance(traffic_name, dict)
               else mf.load_traffic(traffic_name))
    gen = mf.load_module("generators", traffic["generator"])
    return warmup.plan(gen.shapes(traffic), ENGINE)


def oneshot(plan):
    return {(w[0][0], len(w)) for w in plan if w[0][1] == 1 and w[0][0] <= 512}


def test_chat_paced_warms_every_bucket_at_every_group_size():
    plan = plan_for("chat-paced")
    assert oneshot(plan) == {(b, g) for b in ENGINE["prefill_buckets"] for g in (1, 2, 4)}
    # chunked extends: the largest bucket plus each bucket
    assert {w[0][0] for w in plan if w[0][0] > 512 and w[0][1] == 1} == {
        544, 576, 640, 768, 1024}
    # one decode burst in every context window the lengths reach
    assert [w[0] for w in plan if w[0][1] == 9] == [(32, 9), (256, 9), (512, 9), (1024, 9)]


def test_decode_saturated_warms_its_two_buckets_only():
    plan = plan_for("decode-saturated")
    assert oneshot(plan) == {(b, g) for b in (64, 128) for g in (1, 2, 4, 8)}
    assert not [w for w in plan if w[0][0] > 512]
    # contexts reach 128 + 512 = 640: windows 256, 512 and 1024, not 2048
    assert [w[0][0] for w in plan if w[0][1] == 9] == [32, 256, 512]


def test_sessions_warm_the_extend_path_and_no_one_shot_group():
    from tests.benchmark.test_generators import SESSIONS_MIX

    plan = plan_for(SESSIONS_MIX)
    assert not {g for _, g in oneshot(plan) if g > 1}
    assert {w[0][0] for w in plan if w[0][0] > 512 and w[0][1] == 1} == {
        544, 576, 640, 768, 1024}
    assert len([w for w in plan if w[0][1] == 9]) == 4
