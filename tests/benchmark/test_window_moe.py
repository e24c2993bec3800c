"""The window-and-global decoder's files in the benchmark (PR 45): its
configuration against the catalog row it was cut from, the operations and
bytes of benchmark/roofline/window_moe.py and the six readers on hand-worked
numbers, what the readers give a program that has no such counters
(nothing), benchmark/check_window.py and its controls at a CI size, and the
new cell's path end to end on the CPU (`run.py --rehearse`).

Every assertion about `BENCHMARK.json` is of MEMBERSHIP and CONTENT, never
of position or of how many cells or configurations there are: the next PR
appends, and these tests must not turn red for it."""

import json
import os
import subprocess
import sys
import tempfile

import pytest

from benchmark import manifest as mf
from benchmark import peaks

MANIFEST = mf.load()
NAME = "mimo-v2-5-l7"
CELL = NAME + ".reason-long-out"
CONFIG = mf.load_config(MANIFEST, NAME)
ROOFLINE = mf.load_module("roofline", "window_moe")
HERE = os.path.dirname(os.path.abspath(__file__))
REHEARSAL = os.path.join(HERE, "rehearsal_window", "BENCHMARK.json")
V5E = peaks.peaks_for("TPU v5 lite")
LAYER = {"model.window_moe_decode_roofline": "model step",
         "kernel.window_decode_roofline": "kernels",
         "kernel.window_global_decode_roofline": "kernels",
         "kernel.window_held_experts_roofline": "kernels",
         "attn.window_kv_tokens_share": "model step",
         "moe.window_held_assignment_share": "model step"}
READERS = tuple(LAYER)

with open(os.path.join(mf.HERE, "settings.json")) as f:
    SETTINGS = json.load(f)

EXPERT = 3 * 4096 * 2048  # one routed expert's three matrices
N_PARAMS = 3_429_955_392


def test_the_manifest_is_sound_and_the_cell_is_the_issues():
    assert mf.check(MANIFEST) == []
    cell = mf.cell(MANIFEST, CELL)
    assert cell == {**cell, "chips": 1, "config": NAME,
                    "traffic": "reason-long-out"}
    for said in ("closed loop", "32 callers", "64-128 in", "4,096 out",
                 "4.2k", "5 window", "2 global", "6 mixtures", "1.0", "16"):
        assert said in cell["why"], said
    assert len(cell["why"]) <= 200
    assert [w["name"] for w in MANIFEST["workloads"]
            if w["config"] == NAME] == [CELL]
    traffic = mf.load_traffic("reason-long-out")
    assert {k: traffic[k] for k in (
        "generator", "clients", "prompt", "max_tokens", "ramp_s",
        "start_after_tokens", "requests_per_client", "max_prefill_group")} == {
        "generator": "closed_loop", "clients": 32,
        "prompt": {"kind": "uniform", "lo": 64, "hi": 128},
        "max_tokens": 4096, "ramp_s": 16, "start_after_tokens": 2,
        "requests_per_client": 8, "max_prefill_group": 8}
    # the engine holds a request whole: 128 + 4,096 under the capacity
    assert 128 + 4096 <= CONFIG["engine"]["slot_capacity"]
    per_layer = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in READERS:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == "tpot_p50_s"
        assert per_layer[name]["layer"] == LAYER[name]
        assert per_layer[name]["unit"] == "%"
        assert os.path.isfile(os.path.join(mf.HERE, "layer_metrics",
                                           name + ".py"))
    reported = {m["name"] for m in mf.metrics_for(MANIFEST, "per_layer", CELL)}
    assert set(READERS) | {"model.decode_step_s", "sched.host_share",
                           "device.idle_share", "device.hbm_peak_bytes",
                           "engine.compiles_in_window",
                           "engine.programs_built_in_window"} <= reported
    # the other families' readers list other cells: none was given this one
    for m in MANIFEST["per_layer"]:
        if m["name"] not in READERS:
            assert CELL not in m.get("workloads", []), m["name"]
    assert {m["name"] for m in mf.metrics_for(MANIFEST, "end_to_end", CELL)
            } == {"tpot_p50_s", "setup_s"}


# MiMo-V2.5's config.json as the catalog
# (/opt/skills/guides/model-configs/architectures.jsonl) has it, carried
# here so that the test holds where the catalog is not installed.
PATTERN = [0, 1, 1, 1, 1] + [0, 1, 1, 1, 1, 1] * 7 + [0]
PUBLISHED = {
    "attention_bias": False, "attention_chunk_size": 128,
    "attention_value_scale": 0.707,
    "attention_projection_layout": "fused_qkv",
    "add_full_attention_sink_bias": False,
    "add_swa_attention_sink_bias": True, "swa_num_key_value_heads": 8,
    "swa_num_attention_heads": 64, "swa_head_dim": 192,
    "swa_v_head_dim": 128, "head_dim": 192, "hidden_act": "silu",
    "hidden_size": 4096, "hybrid_block_size": None,
    "hybrid_layer_pattern": PATTERN, "intermediate_size": 16384,
    "layernorm_epsilon": 1e-05, "max_position_embeddings": 1048576,
    "model_type": "mimo_v2", "moe_intermediate_size": 2048,
    "moe_layer_freq": [0] + [1] * 47, "n_group": 1, "n_routed_experts": 256,
    "n_shared_experts": None, "norm_topk_prob": True,
    "num_attention_heads": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "partial_rotary_factor": 0.334,
    "rope_scaling": {"rope_type": "default", "type": "default"},
    "rope_theta": 10000000, "routed_scaling_factor": None,
    "scoring_func": "sigmoid", "sliding_window": 128,
    "sliding_window_size": 128, "swa_rope_theta": 10000,
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 152576,
}
SOURCE = "https://huggingface.co/XiaomiMiMo/MiMo-V2.5/blob/main/config.json"
CUTS = {"num_hidden_layers": (48, 7), "n_routed_experts": (256, 16),
        "vocab_size": (152576, 19072)}
WITH_THE_DEPTH = {"hybrid_layer_pattern", "moe_layer_freq"}  # a row a layer


def test_the_configuration_holds_the_published_keys_and_exactly_three_cuts():
    """What `test_manifest.py::test_a_configuration_file_cuts_depth_only`
    means, against this model's own widths (that test asserts Mistral-7B's
    of every configuration and fails for this one as a new case: PERF.md
    section 7)."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):  # the copy above is the catalog's row
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "MiMo-V2.5")
        assert (row["config"], row["source_url"]) == (PUBLISHED, SOURCE)
    differs = {k for k, v in PUBLISHED.items() if CONFIG.get(k, "-") != v}
    assert differs - WITH_THE_DEPTH == set(CUTS) == set(CONFIG["reduced"])
    # the two per-layer lists are cut with the depth: their first seven rows
    for key in WITH_THE_DEPTH:
        assert CONFIG[key] == PUBLISHED[key][:7]
    assert CONFIG["hybrid_layer_pattern"] == [0, 1, 1, 1, 1, 0, 1]
    assert CONFIG["moe_layer_freq"] == [0, 1, 1, 1, 1, 1, 1]
    entry = mf.config_entry(MANIFEST, NAME)
    assert sorted(entry["reduced"]) == sorted(CUTS)
    assert entry["file"] == "benchmark/configs/mimo-v2-5-l7.json"
    assert entry["source"] == CONFIG["source"] == SOURCE
    assert len(entry["why"]) <= 200
    for key, (published, here) in CUTS.items():
        cut = CONFIG["reduced"][key]
        assert (cut["published"], cut["here"], CONFIG[key]) == (
            published, here, here)
        assert not mf.WIDTH_RE.search(key)  # no width is cut
    # every width as published
    assert (CONFIG["hidden_size"], CONFIG["intermediate_size"],
            CONFIG["moe_intermediate_size"], CONFIG["num_attention_heads"],
            CONFIG["num_key_value_heads"], CONFIG["swa_num_key_value_heads"],
            CONFIG["head_dim"], CONFIG["v_head_dim"],
            CONFIG["sliding_window"], CONFIG["num_experts_per_tok"]) == (
        4096, 16384, 2048, 64, 4, 8, 192, 128, 128, 8)
    # the floors: a whole period and four layers, 8 or more experts held, an
    # eighth of the rows; five window layers to one global in the period
    assert CONFIG["hybrid_layer_pattern"][1:].count(1) == 5
    assert CONFIG["n_routed_experts"] >= 8
    assert CONFIG["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert CONFIG["expert_parallel"] == {"chips": 16, "chip": 0,
                                         "experts": 256}
    assert CONFIG["torch_dtype"] == "bfloat16"
    assert set(CONFIG["assumed"]) >= {
        "sink", "e_score_correction_bias", "weights", "rotary",
        "attention_value_scale", "sliding_window", "rms_norm_eps",
        "attention_chunk_size", "outside"}
    assert "standard deviation 1" in CONFIG["assumed"]["sink"]
    assert "0.02" in CONFIG["assumed"]["e_score_correction_bias"]
    assert "FIRST 64" in CONFIG["assumed"]["rotary"]
    assert "NO layer" in CONFIG["assumed"]["attention_chunk_size"]
    for said in ("64-chip", "16 chips", "1.0 a held expert a step",
                 "16 a held expert a step", "16 TIMES"):
        assert said in CONFIG["deployment"], said
    assert "6.860 GB" in CONFIG["bytes"]
    correctness = CONFIG["correctness"]
    assert correctness["reference"] == "mimo_v2"
    assert (correctness["prefill_tokens"], correctness["extend_chunks"],
            correctness["extend_tokens"]) == (256, 2, 64)
    # prefill, extend and decode each run past the window; decode from 384
    assert correctness["decode_steps"] >= 16
    for text in (correctness["why"], *CONFIG["assumed"].values()):
        assert "TO BE SET" not in text and "PROVISIONAL" not in text
    engine = CONFIG["engine"]
    assert (engine["num_slots"], engine["slot_capacity"],
            engine["kv_page_size"], engine["kv_pages"], engine["decode_burst"],
            engine["prefix_cache"]) == (32, 4352, 128, 32 * 34 + 32 + 1, 8,
                                        False)
    assert engine["prefill_buckets"] == [32, 64, 128, 256, 512]


def test_the_program_reads_the_configuration_as_two_caches_and_a_share():
    from benchmark import launcher
    from llmlb_tpu.engine.scheduler import kv_page_bytes
    from llmlb_tpu.models import family_for, mimo_v2

    cfg = launcher.build_cfg(CONFIG)
    assert family_for(cfg) is mimo_v2
    assert (cfg.num_layers, cfg.pattern, cfg.moe_pattern, cfg.router_experts,
            cfg.held_experts, cfg.experts_per_token, cfg.num_heads,
            cfg.num_kv_heads, cfg.window_kv_heads, cfg.head_dim_,
            cfg.v_head_dim, cfg.rotary_dim, cfg.sliding_window,
            cfg.vocab_size) == (
        7, (0, 1, 1, 1, 1, 0, 1), (0, 1, 1, 1, 1, 1, 1), 256, (0, 16), 8, 64,
        4, 8, 192, 128, 64, 128, 19072)
    assert (cfg.rope_theta, cfg.window_rope_theta, cfg.value_scale,
            cfg.routed_scaling_factor, cfg.window_sink) == (
        1e7, 1e4, 0.707, 1.0, True)
    # the two caches, as the file's arithmetic has them
    record = mimo_v2.FAMILY
    assert record.kv_pool_layers(cfg) == 2
    assert record.kv_token_layer_bytes(cfg) == 2560
    assert kv_page_bytes(cfg, 128) == 2 * 128 * 2560 == 655_360
    assert 1121 * kv_page_bytes(cfg, 128) / 1e9 == pytest.approx(0.735,
                                                                  abs=1e-3)
    assert record.state_slot_bytes(cfg) == 5 * 128 * 8 * 320 * 2 == 3_276_800
    import jax

    shapes = jax.eval_shape(lambda k: mimo_v2.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    assert sum(v.size for v in shapes.values()) == N_PARAMS
    assert N_PARAMS * 2 / 1e9 == pytest.approx(6.860, abs=1e-3)
    assert shapes["we_up"].shape == (6, 16, 4096, 2048)
    assert shapes["router"].shape == (6, 4096, 256)
    assert shapes["g_wk"].shape == (2, 4096, 4 * 192)
    assert shapes["w_wv"].shape == (5, 4096, 8 * 128)
    assert shapes["w_sink"].shape == (5, 64) and "g_sink" not in shapes
    assert shapes["dense_wg"].shape == (1, 4096, 16384)
    pool = jax.eval_shape(lambda: mimo_v2.init_kv_pages(cfg, 1121, 128,
                                                        num_slots=32))
    assert pool[0].pages.shape == (2, 1121, 128, 4 * 192)
    assert pool[1].state.shape == (5, 33, 128, 8, 128)


def test_the_reference_is_found_by_the_configurations_name():
    from benchmark import reference

    module = reference.module_for(CONFIG)
    assert module.__name__.endswith("mimo_v2")
    assert module.FOLLOWS == "routing"
    assert module.held_range(CONFIG) == (0, 16)
    assert module.held_range({**CONFIG, "expert_parallel": {
        "chips": 16, "chip": 5, "experts": 256}}) == (80, 16)
    assert module.rotary_numbers(CONFIG) == 64
    with open(module.__file__) as f:
        source = f.read()
    # independent of the program's ops: no kernel, cache or batching
    assert "llmlb_tpu" not in source.replace("llmlb_tpu/", "")
    assert 'default_matmul_precision("highest")' in source
    assert "pallas" not in source and "def generate" in source


def test_roofline_accounts_on_hand_worked_numbers():
    assert ROOFLINE.expert_params(CONFIG) == EXPERT == 25_165_824
    assert ROOFLINE.expert_bytes(CONFIG) == 2 * EXPERT
    assert (ROOFLINE.layers(CONFIG, ROOFLINE.WINDOW),
            ROOFLINE.layers(CONFIG, ROOFLINE.GLOBAL),
            ROOFLINE.moe_layers(CONFIG), ROOFLINE.held_slots(CONFIG)) == (
        5, 2, 6, 96)
    assert ROOFLINE.cell_numbers(CONFIG, ROOFLINE.WINDOW) == 8 * 320
    assert ROOFLINE.cell_numbers(CONFIG, ROOFLINE.GLOBAL) == 4 * 320
    # 32 rows past the window: 32 x 128 cells in each of 5 layers
    w = ROOFLINE.window_decode(CONFIG, cells=32 * 128 * 5)
    assert w["bytes"] == 32 * 128 * 5 * 5120 == 104_857_600
    assert w["flops"] == 2 * 32 * 128 * 5 * 64 * 320
    # 64,000 live tokens in each of 2 global layers
    w = ROOFLINE.global_decode(CONFIG, cells=2 * 64_000)
    assert w["bytes"] == 2 * 64_000 * 2560
    # grouped products: 61 experts touched by 96 held assignments
    w = ROOFLINE.held_experts(CONFIG, experts_touched=61, assignments=96)
    assert w["flops"] == 96 * 2 * EXPERT
    assert w["bytes"] == 61 * 2 * EXPERT + 96 * (2 * 4096 + 3 * 2048) * 2
    # a step of 32 rows at contexts of 2k that touches 61 of the 96 held
    engine = {"param_bytes": 2 * N_PARAMS, "n_params": N_PARAMS}
    w = ROOFLINE.decode_step(CONFIG, engine, live_tokens=64_000, rows=32,
                             experts_touched=61)
    embed = 19072 * 4096
    assert w["bytes"] == (2 * N_PARAMS - embed * 2 - (96 - 61) * 2 * EXPERT
                          + 2 * 64_000 * 2560 + 32 * 128 * 5 * 5120)
    active = N_PARAMS - embed - 96 * EXPERT + 6 * 8 * (16 / 256) * EXPERT
    assert w["flops"] == pytest.approx(
        2 * active * 32 + 2 * 64 * 320 * (2 * 64_000 + 32 * 128 * 5))
    # the issue's arithmetic: 5.0-5.7 GB a step, 6.1-7.0 ms of reading
    assert 5.0e9 < w["bytes"] < 5.7e9
    assert 6.1e-3 < w["bytes"] / V5E["hbm_bytes_per_s"] < 7.0e-3
    # rows shorter than the window hold their own length, not 128 cells
    short = ROOFLINE.decode_step(CONFIG, engine, live_tokens=32 * 40, rows=32,
                                 experts_touched=61)
    assert short["bytes"] == (w["bytes"] - 2 * 64_000 * 2560
                              - 32 * 128 * 5 * 5120 + 32 * 40 * (2 * 2560
                                                                 + 5 * 5120))


def decode_record(ts, *, rows=32, burst=8, touched=480, here=768,
                  context=2000):
    return {"kind": "decode", "ts": ts, "total_s": 0.1, "active_slots": rows,
            "tokens": rows * burst, "experts_touched": touched,
            "expert_assignments": here,
            "assignments_elsewhere": rows * burst * 6 * 8 - here,
            "expert_load_max": 4,
            "window_kv_tokens": rows * burst * 5 * min(context, 128),
            "global_kv_tokens": rows * burst * 2 * context}


def collected(steps, trace=None):
    reqs = [{"first_s": 0.0, "last_s": 60.0, "prompt_tokens": 100,
             "words": 4096} for _ in range(32)]
    return {"config": CONFIG, "steps": steps, "trace": trace, "peaks": V5E,
            "seconds": 51, "settings": SETTINGS, "requests": reqs,
            "engine": {"decode_burst": 8, "param_bytes": 2 * N_PARAMS,
                       "n_params": N_PARAMS}}


def read(name, c):
    return mf.load_module("layer_metrics", name).read(c)


def test_counter_readers_on_hand_worked_numbers():
    c = collected([decode_record(10.0), decode_record(10.1, context=100)])
    window = 256 * 5 * (128 + 100)
    assert read("attn.window_kv_tokens_share", c) == pytest.approx(
        100 * window / (window + 256 * 2 * 2100))
    # at a context of 128, five rings to two pages; at 4k, 7%
    for context, share in ((128, 100 * 5 / 7), (4096, 100 * 640 / 8832)):
        assert read("attn.window_kv_tokens_share", collected(
            [decode_record(10.0, context=context)])) == pytest.approx(share)
    assert read("moe.window_held_assignment_share", c) == pytest.approx(
        100 * 768 / 12288)  # 6.25: 16 of 256 under uniform routing
    # a prefill between the bursts counts in neither
    steps = [decode_record(10.0), {
        "kind": "prefill", "ts": 10.3, "total_s": 0.05, "tokens": 700,
        "active_slots": 8, "experts_touched": 96, "expert_assignments": 9000,
        "assignments_elsewhere": 24600, "expert_load_max": 90,
        "window_kv_tokens": 3500, "global_kv_tokens": 1400}]
    assert read("moe.window_held_assignment_share", collected(steps)
                ) == pytest.approx(6.25)
    assert read("attn.window_kv_tokens_share", collected(steps)
                ) == pytest.approx(100 * 640 / (640 + 4000))


def test_trace_readers_on_hand_worked_numbers():
    steps = [decode_record(100.1),  # in the traced part
             decode_record(90.0, touched=400)]  # before it
    trace = {"wall_start": 99.0, "wall_stop": 107.0,
             "ops": {"grouped_expert_matmul_bf16_256_2048_":
                     {"time_s": 0.020, "count": 96},
                     "grouped_expert_matmul_f32_256_4096_":
                     {"time_s": 0.012, "count": 48},
                     "paged_window_decode_bf16_32_64_128_":
                     {"time_s": 0.004, "count": 40},
                     "paged_flat_decode_bf16_32_64_128_":
                     {"time_s": 0.006, "count": 16},
                     "paged_flash_decode_bf16_32_64_128_":  # another kernel's
                     {"time_s": 7.0, "count": 1},
                     "fusion_bf16_32_12288_": {"time_s": 9.0, "count": 1}},
             "modules": {"jit_many(123)": {"count": 8, "time_s": 0.8,
                                           "median_s": 0.1}}}
    c = collected(steps, trace)
    w = ROOFLINE.held_experts(CONFIG, experts_touched=480, assignments=768)
    want, bound = peaks.roofline_share_pct(w["flops"], w["bytes"], 0.032, V5E)
    assert read("kernel.window_held_experts_roofline", c) == pytest.approx(
        want)
    assert 0 < want < 100 and bound == "memory"
    # the traced record's ring cells over the window kernel's rows
    w = ROOFLINE.window_decode(CONFIG, cells=256 * 5 * 128)
    want, _ = peaks.roofline_share_pct(w["flops"], w["bytes"], 0.004, V5E)
    assert read("kernel.window_decode_roofline", c) == pytest.approx(want)
    assert 0 < want < 100
    w = ROOFLINE.global_decode(CONFIG, cells=256 * 2 * 2000)
    want, _ = peaks.roofline_share_pct(w["flops"], w["bytes"], 0.006, V5E)
    assert read("kernel.window_global_decode_roofline", c) == pytest.approx(
        want)
    assert 0 < want < 100
    # both records are the window's: (480 + 400) / 16 experts a step
    live = 32 * (100 + 4096 * 47 / 60)
    w = ROOFLINE.decode_step(CONFIG, c["engine"], live_tokens=live, rows=32,
                             experts_touched=(480 + 400) / 16)
    want, _ = peaks.roofline_share_pct(w["flops"], w["bytes"], 0.1 / 8, V5E)
    assert read("model.window_moe_decode_roofline", c) == pytest.approx(
        want, rel=1e-3)
    assert 0 < want < 100


@pytest.mark.parametrize("name", READERS)
def test_readers_give_nothing_for_a_program_without_the_counters(name):
    """The parent commit, and every other family: step records without the
    two attention counters, a trace without the kernels, another
    configuration. Nothing, and no exception."""
    plain = [{"kind": "decode", "ts": 100.0, "total_s": 0.2, "tokens": 256,
              "active_slots": 32}]
    hybrid = [{**plain[0], "experts_touched": 900, "expert_assignments": 1500,
               "assignments_elsewhere": 1500, "expert_load_max": 9}]
    trace = {"wall_start": 99.0, "wall_stop": 107.0,
             "ops": {"paged_flash_decode_bf16_32_8_4_128_":
                     {"time_s": 1.0, "count": 10}},
             "modules": {"jit_many(1)": {"count": 8, "time_s": 1.6,
                                         "median_s": 0.2}}}
    assert read(name, collected(plain, trace)) is None
    assert read(name, collected([], None)) is None
    full = {"wall_start": 99.0, "wall_stop": 107.0, "ops": {
        "grouped_expert_matmul_bf16_384_768_": {"time_s": 1.0, "count": 10},
        "paged_flash_decode_bf16_32_32_128_": {"time_s": 1.0, "count": 10}},
        "modules": trace["modules"]}
    for other in (c["name"] for c in MANIFEST["configs"] if c["name"] != NAME):
        c = {**collected(hybrid, full),
             "config": mf.load_config(MANIFEST, other)}
        assert read(name, c) is None, other


def test_the_cells_path_runs_end_to_end_on_the_cpu_at_a_ci_size():
    """`run.py --rehearse --trace 1` on the family through the real
    launcher, gateway and generator: `correct` with the routing heard
    (prefill, two extends and 20 decode steps, each past a ring of 16),
    every request served, the two attention counters on the window's
    records and the counter readers in the line."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(mf.ROOT, "benchmark", "run.py"),
         "--manifest", REHEARSAL, "--workload", "tiny-window.closed",
         "--seed", "2147483655", "--seconds", "3", "--trace", "1",
         "--rehearse"],
        env=env, capture_output=True, text=True, timeout=280, cwd=mf.ROOT)
    assert proc.returncode == 4, proc.stderr[-3000:]
    split, line = map(json.loads, proc.stdout.strip().splitlines()[-2:])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert split["correctness"]["grounds"] == []
    assert split["correctness"]["positions_compared"] == 1 + 2 + 20
    assert split["compiles_in_window"] == 0
    # contexts of 8 to 72 around a ring of 16; the second half of 8 held
    assert 20 <= line["metrics"]["attn.window_kv_tokens_share"]["value"] <= 72
    assert 20 <= line["metrics"]["moe.window_held_assignment_share"][
        "value"] <= 80
    assert line["metrics"]["engine.programs_built_in_window"]["value"] == 0
    # device-trace readers find no device plane on the CPU: left out
    assert not set(line["metrics"]) & set(READERS[:4])
    with open(os.path.join(mf.ROOT, ".bench_run", "tiny-window.closed",
                           "last_run.json")) as f:
        steps = json.load(f)["steps"]
    decodes = [r for r in steps if r["kind"] == "decode"]
    assert decodes
    for r in decodes:  # 6 mixture layers x 2 a token; 5 rings of 16 cells
        assert (r["expert_assignments"] + r["assignments_elsewhere"]
                == r["tokens"] * 6 * 2)
        assert 0 < r["window_kv_tokens"] <= r["tokens"] * 5 * 16
        assert r["global_kv_tokens"] >= r["tokens"] * 2 * 8


# --- benchmark/check_window.py: the controls of the new layers ---------------

def _window(cases, capsys, monkeypatch, seed="5"):
    from benchmark import check_window

    rehearsal = os.path.dirname(REHEARSAL)
    monkeypatch.setattr(sys, "argv", [
        "check_window.py", "--config",
        os.path.join(rehearsal, "configs", "debug-mimo-tiny.json"),
        "--base", rehearsal, "--seeds", seed, "--cases", cases])
    monkeypatch.setattr(check_window, "ROOT", tempfile.mkdtemp())  # its log
    monkeypatch.setenv("LLMLB_INIT_TIMEOUT", "0")  # no watchdog under capsys
    assert check_window.main() == 0
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()
             if line.startswith("{")]
    return {line["case"]: line for line in lines}


def test_the_sound_program_passes_and_every_control_is_refused(capsys,
                                                               monkeypatch):
    from benchmark import check_window

    got = _window(check_window.CASES, capsys, monkeypatch)
    assert set(got) == set(check_window.CASES.split(","))
    for case in ("program", "interleaved_decode"):
        sound = got[case]["result"]
        assert sound["ok"] is True and sound["max_rel_rms_err"] < 1e-4, case
        assert sound["dropped_assignments"] == 0
    assert 0 < got["program"]["chosen_held_share"] < 1
    for case in ("no_window", "window_129", "no_sink", "no_value_scale",
                 "full_rotary", "one_rope_base", "window_4_kv_heads",
                 "int8_weights", "zeroed_chosen_expert"):
        result = got[case]["result"]
        assert result["ok"] is False, case
        assert "logits" in result["grounds"], case
    assert got["zeroed_chosen_expert"]["read_by"] > 0
    for case in ("no_window", "no_value_scale", "full_rotary",
                 "window_4_kv_heads"):
        assert got[case]["result"]["max_rel_rms_err"] > 0.05, case
    wrong = got["unbiased_choice"]["result"]
    assert "choice_is_own_topk" in wrong["grounds"]
    assert wrong["max_rel_rms_err"] < 1e-4


def test_the_precision_control_leaves_the_true_weights_behind(capsys,
                                                              monkeypatch):
    got = _window("int8_weights,program", capsys, monkeypatch, seed="7")
    assert got["int8_weights"]["result"]["ok"] is False
    assert got["program"]["result"]["ok"] is True
    assert (got["program"]["result"]["max_rel_rms_err"] < 1e-4
            < got["int8_weights"]["result"]["max_rel_rms_err"])
