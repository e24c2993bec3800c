"""The window-band mixture's files in the benchmark (PR 52): its
configuration against the catalog row it was cut from, the operations and
bytes of benchmark/roofline/band_moe.py and the six readers on hand-worked
numbers, what the readers give a program that has no such counters
(nothing), benchmark/check_band.py and its controls at a CI size, and the
new cell's path end to end on the CPU (`run.py --rehearse`).

Every assertion about `BENCHMARK.json` is of MEMBERSHIP and CONTENT, found
by name, never of position or of how many cells or configurations there
are: the next PR appends, and these tests must not turn red for it."""

import json
import os
import subprocess
import sys
import tempfile

import pytest

from benchmark import manifest as mf
from benchmark import peaks

MANIFEST = mf.load()
NAME = "trinity-mini-l16"
CELL = NAME + ".reason-long-out"
CONFIG = mf.load_config(MANIFEST, NAME)
ROOFLINE = mf.load_module("roofline", "band_moe")
HERE = os.path.dirname(os.path.abspath(__file__))
REHEARSAL = os.path.join(HERE, "rehearsal_band", "BENCHMARK.json")
V5E = peaks.peaks_for("TPU v5 lite")
LAYER = {
    "model.band_moe_decode_roofline": ("model step", "device_trace", "higher"),
    "kernel.band_window_decode_roofline": ("kernels", "device_trace",
                                           "higher"),
    "kernel.band_global_decode_roofline": ("kernels", "device_trace",
                                           "higher"),
    "kernel.band_held_experts_roofline": ("kernels", "device_trace",
                                          "higher"),
    "attn.band_window_kv_tokens_share": ("model step", "program_counter",
                                         "lower"),
    "moe.band_held_assignment_share": ("model step", "program_counter",
                                       "higher")}
READERS = tuple(LAYER)
SOURCE = "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json"
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
REDUCED = {"num_hidden_layers", "layer_types", "num_experts", "vocab_size"}
N_PARAMS = 2_115_641_088
EXPERT = 3 * 2048 * 1024
W, PS, N_W, N_G, N_MOE = 2048, 128, 12, 4, 14

with open(os.path.join(mf.HERE, "settings.json")) as f:
    SETTINGS = json.load(f)


def test_the_manifest_is_sound_and_the_cell_is_the_issues():
    assert mf.check(MANIFEST) == []
    cell = mf.cell(MANIFEST, CELL)
    assert cell == {**cell, "chips": 1, "config": NAME,
                    "traffic": "reason-long-out"}
    for said in ("closed loop", "32 callers", "64-128", "4,096 out",
                 "12 band decodes", "17 pages", "4 global", "14 mixtures",
                 "2.0 a held expert", "attention 8x"):
        assert said in cell["why"], said
    assert len(cell["why"]) <= 200
    assert [w["name"] for w in MANIFEST["workloads"]
            if w["config"] == NAME] == [CELL]  # no second cell
    traffic = mf.load_traffic("reason-long-out")  # as it was
    assert {k: traffic[k] for k in (
        "generator", "clients", "prompt", "max_tokens", "ramp_s",
        "start_after_tokens", "requests_per_client", "max_prefill_group")} == {
        "generator": "closed_loop", "clients": 32,
        "prompt": {"kind": "uniform", "lo": 64, "hi": 128},
        "max_tokens": 4096, "ramp_s": 16, "start_after_tokens": 2,
        "requests_per_client": 8, "max_prefill_group": 8}
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name, (layer, source, better) in LAYER.items():
        assert by_name[name] == {
            "name": name, "unit": "%", "better": better, "source": source,
            "layer": layer, "moves": "tpot_p50_s", "workloads": [CELL]}
        assert os.path.exists(os.path.join(mf.HERE, "layer_metrics",
                                           name + ".py"))
    # the accepted readers that list their cells do not list this one
    for m in MANIFEST["per_layer"]:
        if m["name"] not in LAYER and "workloads" in m:
            assert CELL not in m["workloads"], m["name"]
    e2e = {m["name"] for m in mf.metrics_for(MANIFEST, "end_to_end", CELL)}
    assert e2e == {"tpot_p50_s", "setup_s"}


def test_the_configuration_holds_the_published_keys_and_four_cuts():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Trinity-Mini")
    published = row["config"]
    assert row["source_url"] == SOURCE == CONFIG["source"]
    differs = {k for k, v in published.items() if CONFIG.get(k, "-") != v}
    assert differs == REDUCED == set(CONFIG["reduced"])
    assert published["layer_types"] == PERIOD * 8
    assert CONFIG["layer_types"] == PERIOD * 4 == published["layer_types"][:16]
    for key, was, here in (("num_hidden_layers", 32, 16),
                           ("num_experts", 128, 16),
                           ("vocab_size", 200192, 25088)):
        assert (published[key], CONFIG[key]) == (was, here)
        assert CONFIG["reduced"][key]["published"] == was
        assert CONFIG["reduced"][key]["here"] == here
        assert CONFIG["reduced"][key]["why"]
    entry = mf.config_entry(MANIFEST, NAME)
    assert set(entry["reduced"]) == REDUCED and len(entry["reduced"]) == 4
    assert entry["file"] == "benchmark/configs/trinity-mini-l16.json"
    assert entry["source"] == SOURCE and len(entry["why"]) <= 200
    for key in entry["reduced"]:
        assert not mf.WIDTH_RE.search(key)  # no width is cut
    # every width as published
    assert (CONFIG["hidden_size"], CONFIG["intermediate_size"],
            CONFIG["moe_intermediate_size"], CONFIG["num_attention_heads"],
            CONFIG["num_key_value_heads"], CONFIG["head_dim"],
            CONFIG["sliding_window"], CONFIG["num_experts_per_tok"],
            CONFIG["num_shared_experts"], CONFIG["num_dense_layers"]) == (
        2048, 6144, 1024, 32, 4, 128, 2048, 8, 1, 2)
    # the floors: whole periods and four behind the dense layers, 8 or more
    # experts held, an eighth of the rows in whole lane tiles
    assert CONFIG["num_experts"] >= 8
    assert CONFIG["vocab_size"] * 8 >= published["vocab_size"]
    assert CONFIG["vocab_size"] % 128 == 0
    assert CONFIG["expert_parallel"] == {"chips": 8, "chip": 0,
                                         "experts": 128}
    assert CONFIG["torch_dtype"] == "bfloat16"
    assert set(CONFIG["assumed"]) >= {
        "gate", "qk_norm", "sandwich_norms", "rotary", "sliding_window",
        "expert_bias", "route", "embedding", "shared_expert", "weights",
        "band_page_size"}
    assert "do NOT rotate" in CONFIG["assumed"]["rotary"]
    assert "0.02" in CONFIG["assumed"]["expert_bias"]
    assert "sqrt(hidden_size)" in CONFIG["assumed"]["embedding"]
    assert "counts the position itself" in CONFIG["assumed"]["sliding_window"]
    for said in ("16-chip", "8 chips", "2.0 a held expert a step",
                 "16 a held expert a step", "8 TIMES"):
        assert said in CONFIG["deployment"], said
    assert "4.231 GB" in CONFIG["bytes"]
    correctness = CONFIG["correctness"]
    assert correctness["reference"] == "afmoe"
    # prefill passes the window, the extends wrap the band (2,176 cells),
    # decode runs with the bound active
    assert (correctness["prefill_tokens"], correctness["extend_chunks"],
            correctness["extend_tokens"]) == (2176, 2, 64)
    assert correctness["decode_steps"] >= 16
    for text in (correctness["why"], *CONFIG["assumed"].values()):
        assert "TO BE SET" not in text and "PROVISIONAL" not in text
    engine = CONFIG["engine"]
    assert (engine["num_slots"], engine["slot_capacity"],
            engine["kv_page_size"], engine["kv_pages"], engine["decode_burst"],
            engine["prefix_cache"]) == (32, 4352, 128, 32 * 34 + 32 + 1, 8,
                                        False)
    assert engine["prefill_buckets"] == [32, 64, 128, 256, 512]


def test_the_program_reads_the_configuration_as_pages_a_band_and_a_share():
    import jax

    from benchmark import launcher
    from llmlb_tpu.engine.scheduler import kv_page_bytes
    from llmlb_tpu.models import afmoe, family_for

    cfg = launcher.build_cfg(CONFIG)
    assert family_for(cfg) is afmoe
    assert (cfg.num_layers, cfg.layer_types, cfg.router_experts,
            cfg.held_experts, cfg.experts_per_token, cfg.num_heads,
            cfg.num_kv_heads, cfg.head_dim_, cfg.sliding_window,
            cfg.band_page_size, cfg.band_pages, cfg.num_dense_layers,
            cfg.num_moe_layers, cfg.vocab_size) == (
        16, tuple(PERIOD * 4), 128, (0, 16), 8, 32, 4, 128, W, PS, 17, 2,
        N_MOE, 25088)
    assert (cfg.rope_theta, cfg.route_scale, cfg.route_norm,
            cfg.mup_enabled, cfg.rms_eps) == (1e4, 2.826, True, True, 1e-5)
    # the two caches, as the file's arithmetic has them
    record = afmoe.FAMILY
    assert record.kv_pool_layers(cfg) == N_G
    assert record.kv_token_layer_bytes(cfg) == 2048
    assert kv_page_bytes(cfg, PS) == N_G * PS * 2048 == 1_048_576
    assert 1121 * kv_page_bytes(cfg, PS) / 1e9 == pytest.approx(1.175,
                                                                 abs=1e-3)
    assert record.state_slot_bytes(cfg) == N_W * (W + PS) * 2048
    assert 33 * record.state_slot_bytes(cfg) / 1e9 == pytest.approx(
        1.765, abs=1e-3)
    shapes = jax.eval_shape(lambda k: afmoe.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    assert sum(v.size for v in shapes.values()) == N_PARAMS
    assert N_PARAMS * 2 / 1e9 == pytest.approx(4.231, abs=1e-3)
    assert shapes["we_up"].shape == (N_MOE, 16, 2048, 1024)
    assert shapes["router"].shape == (N_MOE, 2048, 128)
    assert shapes["router_bias"].dtype == jax.numpy.float32
    assert shapes["w_wgate"].shape == (N_W, 2048, 4096)
    assert shapes["g_wk"].shape == (N_G, 2048, 512)
    assert shapes["w_q_norm"].shape == (N_W, 128)
    assert shapes["dense_wg"].shape == (2, 2048, 6144)
    assert shapes["ws_down"].shape == (N_MOE, 1024, 2048)
    pool = jax.eval_shape(lambda: afmoe.init_kv_pages(cfg, 1121, PS,
                                                      num_slots=32))
    assert pool[0].pages.shape == (N_G, 1121, PS, 4, 128)
    assert pool[1].state.shape == (N_W, 33 * 17, PS, 4, 128)


def test_the_reference_is_found_by_the_configurations_name():
    from benchmark import reference

    module = reference.module_for(CONFIG)
    assert module.__name__.endswith("afmoe")
    assert module.FOLLOWS == "routing"
    assert module.held_range(CONFIG) == (0, 16)
    assert module.held_range({**CONFIG, "expert_parallel": {
        "chips": 8, "chip": 5, "experts": 128}}) == (80, 16)
    with open(module.__file__) as f:
        source = f.read()
    # independent of the program's ops: no kernel, cache, band or batching
    assert "llmlb_tpu" not in source.replace("llmlb_tpu/", "")
    assert 'default_matmul_precision("highest")' in source
    assert "pallas" not in source and "def generate" in source


def test_roofline_accounts_on_hand_worked_numbers():
    assert ROOFLINE.expert_params(CONFIG) == EXPERT == 6_291_456
    assert (ROOFLINE.layers(CONFIG, ROOFLINE.WINDOW),
            ROOFLINE.layers(CONFIG, ROOFLINE.GLOBAL),
            ROOFLINE.moe_layers(CONFIG), ROOFLINE.held_slots(CONFIG)) == (
        N_W, N_G, N_MOE, 224)
    assert ROOFLINE.cell_numbers(CONFIG) == 1024  # 2,048 B a cell
    # 32 rows past the window: 32 x 2,048 cells in each of 12 layers
    w = ROOFLINE.attention_decode(CONFIG, cells=32 * W * N_W)
    assert w["bytes"] == 32 * W * N_W * 2048 == 1_610_612_736
    assert w["flops"] == 4 * 32 * W * N_W * 32 * 128
    share, bound = peaks.roofline_share_pct(w["flops"], w["bytes"], 2.4e-3,
                                            V5E)
    assert bound == "memory" and 80 < share < 84  # 1.97 ms of 2.4
    # rows at 4k in each of 4 global layers
    w = ROOFLINE.attention_decode(CONFIG, cells=32 * 4096 * N_G)
    assert w["bytes"] == 32 * 4096 * N_G * 2048
    # grouped products: 196 experts touched by 448 held assignments
    w = ROOFLINE.held_experts(CONFIG, experts_touched=196, assignments=448)
    assert w["flops"] == 448 * 2 * EXPERT
    assert w["bytes"] == 196 * 2 * EXPERT + 448 * (2 * 2048 + 3 * 1024) * 2
    # a step of 32 rows at contexts of 4k that touches 196 of the 224 held
    engine = {"param_bytes": 2 * N_PARAMS, "n_params": N_PARAMS}
    w = ROOFLINE.decode_step(CONFIG, engine, window_cells=32 * W * N_W,
                             global_cells=32 * 4096 * N_G, rows=32,
                             experts_touched=196)
    embed = 25088 * 2048
    assert w["bytes"] == (2 * N_PARAMS - embed * 2 - (224 - 196) * 2 * EXPERT
                          + (32 * W * N_W + 32 * 4096 * N_G) * 2048)
    active = N_PARAMS - embed - 224 * EXPERT + N_MOE * 8 * (16 / 128) * EXPERT
    assert w["flops"] == pytest.approx(
        2 * active * 32 + 4 * 32 * 128 * (32 * W * N_W + 32 * 4096 * N_G))
    # the issue's arithmetic: weights 3.8 GB, keys and values 2.7 GB at 4k
    assert 3.7e9 < w["bytes"] - (32 * W * N_W + 32 * 4096 * N_G) * 2048 < 3.9e9
    assert (32 * W * N_W + 32 * 4096 * N_G) * 2048 / 1e9 == pytest.approx(
        2.68, abs=0.01)
    assert 7.5e-3 < w["bytes"] / V5E["hbm_bytes_per_s"] < 8.5e-3
    # without the window the 12 layers would read 4k too: 4.3 GB
    assert 32 * 4096 * 16 * 2048 / 1e9 == pytest.approx(4.29, abs=0.01)


def decode_record(ts, *, rows=32, burst=8, touched=1568, here=3584,
                  context=3000):
    n = rows * burst
    pages = (context - 1) // PS - max(context - W, 0) // PS + 1
    return {"kind": "decode", "ts": ts, "total_s": 0.1, "active_slots": rows,
            "tokens": n, "experts_touched": touched,
            "expert_assignments": here,
            "assignments_elsewhere": n * N_MOE * 8 - here,
            "expert_load_max": 6,
            "window_kv_tokens": n * N_W * min(context, W),
            "global_kv_tokens": n * N_G * context,
            "window_pages_read": n * N_W * pages}


def collected(steps, trace=None, config=CONFIG):
    reqs = [{"first_s": 0.0, "last_s": 60.0, "prompt_tokens": 100,
             "words": 4096} for _ in range(32)]
    return {"config": config, "steps": steps, "trace": trace, "peaks": V5E,
            "seconds": 51, "settings": SETTINGS, "requests": reqs,
            "engine": {"decode_burst": 8, "param_bytes": 2 * N_PARAMS,
                       "n_params": N_PARAMS}}


def read(name, c):
    return mf.load_module("layer_metrics", name).read(c)


def test_counter_readers_on_hand_worked_numbers():
    c = collected([decode_record(10.0), decode_record(10.1, context=1000)])
    window = 256 * N_W * (W + 1000)
    assert read("attn.band_window_kv_tokens_share", c) == pytest.approx(
        100 * window / (window + 256 * N_G * 4000))
    # inside the window twelve layers to four; at 4k, 60: it falls as the
    # contexts pass 2,048
    for context, share in ((128, 75.0), (W, 75.0), (4096, 60.0)):
        assert read("attn.band_window_kv_tokens_share", collected(
            [decode_record(10.0, context=context)])) == pytest.approx(share)
    assert read("moe.band_held_assignment_share", c) == pytest.approx(
        100 * 3584 / 28672)  # 12.5: 16 of 128 under uniform routing
    # a prefill between the bursts counts in neither
    steps = [decode_record(10.0), {
        "kind": "prefill", "ts": 10.3, "total_s": 0.05, "tokens": 700,
        "active_slots": 8, "experts_touched": 224, "expert_assignments": 9000,
        "assignments_elsewhere": 69400, "expert_load_max": 90,
        "window_kv_tokens": 8400, "global_kv_tokens": 2800,
        "window_pages_read": 0}]
    assert read("moe.band_held_assignment_share", collected(steps)
                ) == pytest.approx(12.5)
    assert read("attn.band_window_kv_tokens_share", collected(steps)
                ) == pytest.approx(100 * 12 * W / (12 * W + 4 * 3000))


def test_trace_readers_on_hand_worked_numbers():
    steps = [decode_record(100.1),  # in the traced part
             decode_record(90.0, touched=1400)]  # before it
    trace = {"wall_start": 99.0, "wall_stop": 107.0,
             "ops": {"grouped_expert_matmul_bf16_256_1024_":
                     {"time_s": 0.030, "count": 224},
                     "grouped_expert_matmul_f32_256_2048_":
                     {"time_s": 0.018, "count": 112},
                     "paged_band_decode_bf16_32_32_128_":
                     {"time_s": 0.020, "count": 96},
                     "paged_flash_decode_bf16_32_32_128_":
                     {"time_s": 0.012, "count": 32},
                     "paged_window_decode_bf16_32_64_128_":  # another's
                     {"time_s": 7.0, "count": 1},
                     "fusion_bf16_32_4096_": {"time_s": 9.0, "count": 1}},
             "modules": {"jit_many(123)": {"count": 8, "time_s": 0.8,
                                           "median_s": 0.1}}}
    c = collected(steps, trace)
    w = ROOFLINE.held_experts(CONFIG, experts_touched=1568, assignments=3584)
    want, bound = peaks.roofline_share_pct(w["flops"], w["bytes"], 0.048, V5E)
    assert read("kernel.band_held_experts_roofline", c) == pytest.approx(want)
    assert 0 < want < 100 and bound == "memory"
    # the traced record's window cells over the band kernel's rows
    w = ROOFLINE.attention_decode(CONFIG, cells=256 * N_W * W)
    want, _ = peaks.roofline_share_pct(w["flops"], w["bytes"], 0.020, V5E)
    assert read("kernel.band_window_decode_roofline", c) == pytest.approx(want)
    assert 75 < want < 85
    w = ROOFLINE.attention_decode(CONFIG, cells=256 * N_G * 3000)
    want, _ = peaks.roofline_share_pct(w["flops"], w["bytes"], 0.012, V5E)
    assert read("kernel.band_global_decode_roofline", c) == pytest.approx(want)
    assert 0 < want < 100
    # the whole step by the TRACED record's own counters, a step of its 8
    w = ROOFLINE.decode_step(CONFIG, c["engine"], window_cells=32 * N_W * W,
                             global_cells=32 * N_G * 3000, rows=32,
                             experts_touched=1568 / 8)
    want, _ = peaks.roofline_share_pct(w["flops"], w["bytes"], 0.1 / 8, V5E)
    assert read("model.band_moe_decode_roofline", c) == pytest.approx(want)
    assert 55 < want < 65


@pytest.mark.parametrize("name", READERS)
def test_readers_give_nothing_for_a_program_without_the_counters(name):
    """The parent commit, and every other family: step records without the
    attention counters, a trace without the kernels, another configuration.
    Nothing, and no exception."""
    plain = [{"kind": "decode", "ts": 100.0, "total_s": 0.2, "tokens": 256,
              "active_slots": 32}]
    trace = {"wall_start": 99.0, "wall_stop": 107.0,
             "ops": {"paged_flash_decode_bf16_32_8_4_128_":
                     {"time_s": 1.0, "count": 10}},
             "modules": {"jit_many(1)": {"count": 8, "time_s": 1.6,
                                         "median_s": 0.2}}}
    assert read(name, collected(plain, trace)) is None
    assert read(name, collected([], None)) is None
    full = {"wall_start": 99.0, "wall_stop": 107.0, "ops": {
        "grouped_expert_matmul_bf16_256_1024_": {"time_s": 1.0, "count": 10},
        "paged_band_decode_bf16_32_32_128_": {"time_s": 1.0, "count": 10},
        "paged_flash_decode_bf16_32_32_128_": {"time_s": 1.0, "count": 10}},
        "modules": trace["modules"]}
    # this family's records and kernels under another configuration's file
    for other in (c["name"] for c in MANIFEST["configs"] if c["name"] != NAME):
        c = collected([decode_record(100.0)], full,
                      mf.load_config(MANIFEST, other))
        assert read(name, c) is None, other


def test_the_cells_path_runs_end_to_end_on_the_cpu_at_a_ci_size():
    """`run.py --rehearse --trace 1` on the family through the real
    launcher, gateway and generator: `correct` with the routing heard
    (prefill, two extends and 20 decode steps, each past a window of 16 held
    as a band of 3 pages), every request served, the attention counters and
    the band's pages on the window's records and the counter readers in the
    line."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(mf.ROOT, "benchmark", "run.py"),
         "--manifest", REHEARSAL, "--workload", "tiny-band.closed",
         "--seed", "2147483655", "--seconds", "3", "--trace", "1",
         "--rehearse"],
        env=env, capture_output=True, text=True, timeout=280, cwd=mf.ROOT)
    assert proc.returncode == 4, proc.stderr[-3000:]
    split, line = map(json.loads, proc.stdout.strip().splitlines()[-2:])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert split["correctness"]["ok"] is True
    assert split["correctness"]["grounds"] == []
    assert split["correctness"]["positions_compared"] == 1 + 2 + 20
    assert split["correctness"]["max_rel_rms_err"] < 5e-5
    assert split["compiles_in_window"] == 0
    # five window layers of at most 16 cells to one global layer
    assert 40 < line["metrics"]["attn.band_window_kv_tokens_share"][
        "value"] <= 100 * 5 / 6
    assert 20 < line["metrics"]["moe.band_held_assignment_share"]["value"] < 80
    assert line["metrics"]["engine.programs_built_in_window"]["value"] == 0
    # device-trace readers find no device plane on the CPU: left out
    assert not set(line["metrics"]) & set(READERS[:4])
    with open(os.path.join(mf.ROOT, ".bench_run", "tiny-band.closed",
                           "last_run.json")) as f:
        steps = json.load(f)["steps"]
    decodes = [r for r in steps if r["kind"] == "decode"]
    assert decodes
    for r in decodes:  # at most W cells and 3 pages a row and window layer
        assert 0 < r["window_kv_tokens"] <= r["tokens"] * 5 * 16
        assert 0 < r["window_pages_read"] <= r["tokens"] * 5 * 3
        assert r["global_kv_tokens"] >= r["tokens"] * 8


# --- benchmark/check_band.py: the controls of the new layers -----------------

def _band(cases, capsys, monkeypatch, seed="5"):
    from benchmark import check_band

    rehearsal = os.path.dirname(REHEARSAL)
    monkeypatch.setattr(sys, "argv", [
        "check_band.py", "--config",
        os.path.join(rehearsal, "configs", "debug-trinity-tiny.json"),
        "--base", rehearsal, "--seeds", seed, "--cases", cases])
    monkeypatch.setattr(check_band, "ROOT", tempfile.mkdtemp())  # its log
    monkeypatch.setenv("LLMLB_INIT_TIMEOUT", "0")  # no watchdog under capsys
    assert check_band.main() == 0
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()
             if line.startswith("{")]
    return {line["case"]: line for line in lines}


def test_the_sound_program_passes_and_every_control_is_refused(capsys,
                                                               monkeypatch):
    """Through check_band's own loop, the cases that need it (the others go
    through the same variants in tests/engine/test_band_family.py)."""
    from benchmark import check_band

    cases = ("program,interleaved_decode,window_plus_one,no_lower_mask,"
             "no_shared_expert,bf16_router,unfollowed,unbiased_choice,"
             "zeroed_chosen_expert")
    assert set(cases.split(",")) < set(check_band.CASES.split(","))
    got = _band(cases, capsys, monkeypatch)
    assert set(got) == set(cases.split(","))
    for case in ("program", "interleaved_decode"):
        sound = got[case]["result"]
        assert sound["ok"] is True and sound["max_rel_rms_err"] < 5e-5, case
    # `live` false left the band where it was, to the last digit
    assert (got["interleaved_decode"]["result"]["max_rel_rms_err"]
            == got["program"]["result"]["max_rel_rms_err"])
    assert 0 < got["program"]["chosen_held_share"] < 1
    for case in ("window_plus_one", "no_lower_mask", "no_shared_expert",
                 "zeroed_chosen_expert"):
        result = got[case]["result"]
        assert "logits" in result["grounds"], (case, result)
        assert result["max_rel_rms_err"] > 1e-3, case
    assert "router_rel_rms_err" in got["bf16_router"]["result"]["grounds"]
    assert "flips_at_wide_margin" in got["unbiased_choice"]["result"][
        "grounds"]


def test_scores_rounded_in_the_kernels_are_refused(capsys, monkeypatch):
    """The softmax control lives in the Pallas kernels (the interpreter
    here): the sound program through them passes, the rounded one does
    not, and the kernels' own traces are the true ones again after it."""
    import jax

    monkeypatch.setenv("LLMLB_TPU_ATTENTION", "pallas")
    jax.clear_caches()  # the route is read while a program is traced
    try:
        got = _band("program,bf16_softmax", capsys, monkeypatch)
    finally:
        jax.clear_caches()
    soft, sound = got["bf16_softmax"]["result"], got["program"]["result"]
    assert sound["ok"] is True and sound["max_rel_rms_err"] < 5e-5
    assert soft["ok"] is False and soft["max_rel_rms_err"] > 1e-3


def test_the_precision_control_leaves_the_true_weights_behind(capsys,
                                                              monkeypatch):
    got = _band("int8_weights,program", capsys, monkeypatch, seed="7")
    assert got["int8_weights"]["result"]["ok"] is False
    assert got["program"]["result"]["ok"] is True
    assert (got["program"]["result"]["max_rel_rms_err"] < 5e-5
            < got["int8_weights"]["result"]["max_rel_rms_err"])
