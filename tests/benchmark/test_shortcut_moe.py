"""The shortcut-connected mixture's files in the benchmark (PR 41): its
configuration against the catalog row it was cut from, the operations and
bytes of benchmark/roofline/shortcut_moe.py and the five readers on
hand-worked numbers, what the readers give a program that has no such
counters (nothing), benchmark/check_shortcut.py and its controls at a CI
size, and the new cell's path end to end on the CPU (`run.py --rehearse`).

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
NAME = "longcat-flash-omni-l4"
CELL = NAME + ".decode-saturated"
CONFIG = mf.load_config(MANIFEST, NAME)
ROOFLINE = mf.load_module("roofline", "shortcut_moe")
HERE = os.path.dirname(os.path.abspath(__file__))
REHEARSAL = os.path.join(HERE, "rehearsal_shortcut", "BENCHMARK.json")
V5E = peaks.peaks_for("TPU v5 lite")
READERS = ("model.shortcut_moe_decode_roofline",
           "kernel.shortcut_held_experts_roofline",
           "kernel.shortcut_latent_decode_roofline",
           "moe.zero_assignment_share", "moe.real_held_share")
LAYER = {"model.shortcut_moe_decode_roofline": "model step",
         "kernel.shortcut_held_experts_roofline": "kernels",
         "kernel.shortcut_latent_decode_roofline": "kernels",
         "moe.zero_assignment_share": "model step",
         "moe.real_held_share": "model step"}

with open(os.path.join(mf.HERE, "settings.json")) as f:
    SETTINGS = json.load(f)

EXPERT = 3 * 6144 * 2048  # one routed expert's three matrices
N_PARAMS = 5_172_749_312


def test_the_manifest_is_sound_and_has_the_issues_cell():
    assert mf.check(MANIFEST) == []
    cell = mf.cell(MANIFEST, CELL)
    assert cell == {**cell, "chips": 1, "config": NAME,
                    "traffic": "decode-saturated"}
    assert "0.5" in cell["why"] and "16" in cell["why"]
    assert len(cell["why"]) <= 200
    # the configuration has this one cell
    assert [w["name"] for w in MANIFEST["workloads"]
            if w["config"] == NAME] == [CELL]
    traffic = mf.load_traffic("decode-saturated")  # the file that was there
    assert {k: traffic[k] for k in (
        "generator", "clients", "prompt", "max_tokens", "ramp_s",
        "start_after_tokens", "requests_per_client")} == {
        "generator": "closed_loop", "clients": 32,
        "prompt": {"kind": "uniform", "lo": 64, "hi": 128},
        "max_tokens": 512, "ramp_s": 16, "start_after_tokens": 2,
        "requests_per_client": 8}
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


# LongCat-Flash-Omni's config.json (the language model's settings) as the
# catalog (/opt/skills/guides/model-configs/architectures.jsonl) has it,
# carried here so that the test holds where the catalog is not installed.
PUBLISHED = {
    "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
    "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
    "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
    "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
    "n_routed_experts": 512, "max_position_embeddings": 131072,
    "rms_norm_eps": 1e-05, "rope_theta": 10000000, "attention_method": "MLA",
    "zero_expert_num": 256, "zero_expert_type": "identity", "moe_topk": 12,
}
SOURCE = ("https://huggingface.co/meituan-longcat/LongCat-Flash-Omni/blob/"
          "main/config.json")
CUTS = {"num_layers": (28, 4), "n_routed_experts": (512, 16),
        "vocab_size": (131072, 16384)}


def test_the_configuration_holds_the_published_keys_and_three_cuts():
    """What `test_manifest.py::test_a_configuration_file_cuts_depth_only`
    means, against this model's own widths (that test asserts Mistral-7B's
    of every configuration and fails for this one as a new case: PERF.md
    section 7)."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):  # the copy above is the catalog's row
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "LongCat-Flash-Omni")
        assert (row["config"], row["source_url"]) == (PUBLISHED, SOURCE)
    differs = {k for k, v in PUBLISHED.items() if CONFIG.get(k, "-") != v}
    assert differs == set(CUTS) == set(CONFIG["reduced"])
    entry = mf.config_entry(MANIFEST, NAME)
    assert sorted(entry["reduced"]) == sorted(CUTS)
    assert entry["file"] == "benchmark/configs/longcat-flash-omni-l4.json"
    assert entry["source"] == CONFIG["source"] == SOURCE
    assert len(entry["why"]) <= 200
    for key, (published, here) in CUTS.items():
        cut = CONFIG["reduced"][key]
        assert (cut["published"], cut["here"], CONFIG[key]) == (
            published, here, here)
        assert not mf.WIDTH_RE.search(key)  # no width is cut
    # every width as published
    assert (CONFIG["hidden_size"], CONFIG["ffn_hidden_size"],
            CONFIG["expert_ffn_hidden_size"], CONFIG["num_attention_heads"],
            CONFIG["kv_lora_rank"], CONFIG["q_lora_rank"],
            CONFIG["qk_rope_head_dim"], CONFIG["qk_nope_head_dim"],
            CONFIG["v_head_dim"], CONFIG["zero_expert_num"],
            CONFIG["moe_topk"]) == (6144, 12288, 2048, 64, 512, 1536, 64,
                                    128, 128, 256, 12)
    # the floors: four layers, 8 or more experts held, an eighth of the rows
    assert CONFIG["num_layers"] >= 4 and CONFIG["n_routed_experts"] >= 8
    assert CONFIG["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert CONFIG["expert_parallel"] == {"chips": 32, "chip": 0,
                                         "experts": 512}
    assert CONFIG["torch_dtype"] == "bfloat16"
    assert CONFIG["model_type"] == "longcat_flash"
    assert set(CONFIG["assumed"]) >= {
        "norm_topk_prob", "router_bias", "hidden_act", "rope_interleave",
        "tie_word_embeddings", "e_score_correction_bias", "weights",
        "balance"}
    assert "0.1 / 768" in CONFIG["assumed"]["e_score_correction_bias"]
    assert "32 v5e chips" in CONFIG["deployment"]
    assert "0.5 a held expert a step" in CONFIG["deployment"]
    assert "16 a step" in CONFIG["deployment"]
    assert "encoders" in CONFIG["deployment"]
    assert "10.345 GB" in CONFIG["bytes"]
    correctness = CONFIG["correctness"]
    assert correctness["reference"] == "longcat_flash"
    assert (correctness["prefill_tokens"], correctness["extend_chunks"],
            correctness["extend_tokens"], correctness["decode_steps"]) == (
        256, 2, 64, 16)
    for text in (correctness["why"], *CONFIG["assumed"].values()):
        assert "TO BE SET" not in text
    engine = CONFIG["engine"]
    assert (engine["num_slots"], engine["slot_capacity"],
            engine["kv_page_size"], engine["kv_pages"], engine["decode_burst"]
            ) == (32, 2048, 128, 544, 8)
    assert engine["prefill_buckets"] == [32, 64, 128, 256, 512]


def test_the_program_reads_the_configuration_as_double_layers_with_a_share():
    from benchmark import launcher
    from llmlb_tpu.engine.scheduler import kv_page_bytes
    from llmlb_tpu.models import family_for, longcat_flash

    cfg = launcher.build_cfg(CONFIG)
    assert family_for(cfg) is longcat_flash
    assert (cfg.num_layers, cfg.router_experts, cfg.zero_experts,
            cfg.router_width, cfg.held_experts, cfg.experts_per_token,
            cfg.moe_intermediate_size, cfg.intermediate_size, cfg.num_heads,
            cfg.q_lora_rank, cfg.kv_lora_rank, cfg.vocab_size) == (
        4, 512, 256, 768, (0, 16), 12, 2048, 12288, 64, 1536, 512, 16384)
    assert cfg.q_lora_scale == 2.0
    assert cfg.kv_lora_scale == pytest.approx(3.4641, abs=1e-4)
    # a page of the pool and the weights, as the file's arithmetic has them
    assert longcat_flash.kv_pool_layers(cfg) == 8
    assert kv_page_bytes(cfg, 128) == 8 * 128 * (512 + 128) * 2 == 1_310_720
    assert 544 * kv_page_bytes(cfg, 128) / 1e9 == pytest.approx(0.713, abs=1e-3)
    import jax

    shapes = jax.eval_shape(lambda k: longcat_flash.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    assert sum(v.size for v in shapes.values()) == N_PARAMS
    assert N_PARAMS * 2 / 1e9 == pytest.approx(10.345, abs=1e-3)
    assert shapes["s0_we_up"].shape == (4, 16, 6144, 2048)
    assert shapes["s0_router"].shape == (4, 6144, 768)
    assert shapes["s1_wq_b"].shape == (4, 1536, 64 * 192)
    assert "s1_router" not in shapes  # one mixture a layer, with sub-layer 0


def test_the_reference_is_found_by_the_configurations_name():
    from benchmark import reference

    module = reference.module_for(CONFIG)
    assert module.__name__.endswith("longcat_flash")
    assert module.FOLLOWS == "routing"
    assert module.held_range(CONFIG) == (0, 16)
    assert module.real_experts(CONFIG) == 512
    assert module.held_range({**CONFIG, "expert_parallel": {
        "chips": 32, "chip": 5, "experts": 512}}) == (80, 16)
    assert module.dims(CONFIG)["q_scale"] == 2.0
    assert module.dims(CONFIG)["kv_scale"] == pytest.approx(12 ** 0.5)
    with open(module.__file__) as f:
        source = f.read()
    # independent of the program's ops
    assert "llmlb_tpu" not in source.replace("llmlb_tpu/", "")
    assert 'default_matmul_precision("highest")' in source


def test_roofline_accounts_on_hand_worked_numbers():
    assert ROOFLINE.expert_params(CONFIG) == EXPERT == 37_748_736
    assert ROOFLINE.expert_bytes(CONFIG) == 2 * EXPERT
    assert ROOFLINE.attention_layers(CONFIG) == 8
    assert ROOFLINE.held_slots(CONFIG) == 4 * 16
    assert ROOFLINE.router_width(CONFIG) == 768
    # one sub-layer's latent decode for 32 rows over 12,000 live tokens, at
    # 64 heads: roofline/latent_moe.py's account by the row's own keys
    w = ROOFLINE.latent_decode_call(CONFIG, live_tokens=12_000, rows=32)
    assert w["bytes"] == 12_000 * 576 * 2 + 32 * 64 * (2 * 512 + 64) * 2
    assert w["flops"] == 2 * 12_000 * 64 * (2 * 512 + 64)
    # grouped products: 25 experts touched by 32 held assignments
    w = ROOFLINE.held_experts(CONFIG, experts_touched=25, assignments=32)
    assert w["flops"] == 32 * 2 * EXPERT
    assert w["bytes"] == 25 * 2 * EXPERT + 32 * (2 * 6144 + 3 * 2048) * 2
    # a step of 32 rows that touches 25 of the 4 x 16 = 64 experts held
    engine = {"param_bytes": 2 * N_PARAMS, "n_params": N_PARAMS}
    w = ROOFLINE.decode_step(CONFIG, engine, live_tokens=12_000, rows=32,
                             experts_touched=25)
    embed = 16384 * 6144
    assert w["bytes"] == (2 * N_PARAMS - embed * 2 - (64 - 25) * 2 * EXPERT
                          + 12_000 * 8 * 576 * 2)
    active = N_PARAMS - embed - 64 * EXPERT + 4 * 12 * (16 / 768) * EXPERT
    assert w["flops"] == pytest.approx(
        2 * active * 32 + 8 * 2 * 12_000 * 64 * (2 * 512 + 64))
    # the issue's arithmetic: about 7.3 GB a step, at least 8.5 ms of reading
    assert 7.0e9 < w["bytes"] < 7.6e9
    assert 8.5e-3 < w["bytes"] / V5E["hbm_bytes_per_s"] < 9.3e-3
    # the same kernels' names as the other latent mixture's: imported
    latent = mf.load_module("roofline", "latent_moe")
    assert ROOFLINE.LATENT_DECODE_OPS == latent.LATENT_DECODE_OPS
    assert ROOFLINE.ROUTED_EXPERT_OPS == latent.ROUTED_EXPERT_OPS


def decode_record(ts, *, rows=32, burst=8, touched=200, here=256, zero=4096,
                  elsewhere=7936):
    assert here + zero + elsewhere == rows * burst * 4 * 12
    return {"kind": "decode", "ts": ts, "total_s": 0.1, "active_slots": rows,
            "tokens": rows * burst, "experts_touched": touched,
            "expert_assignments": here, "assignments_elsewhere": elsewhere,
            "zero_assignments": zero, "expert_load_max": 3}


def collected(steps, trace=None):
    reqs = [{"first_s": 0.0, "last_s": 60.0, "prompt_tokens": 100,
             "words": 512} for _ in range(32)]
    return {"config": CONFIG, "steps": steps, "trace": trace, "peaks": V5E,
            "seconds": 51, "settings": SETTINGS, "requests": reqs,
            "engine": {"decode_burst": 8, "param_bytes": 2 * N_PARAMS,
                       "n_params": N_PARAMS}}


def read(name, c):
    return mf.load_module("layer_metrics", name).read(c)


def test_counter_readers_on_hand_worked_numbers():
    c = collected([decode_record(10.0),
                   decode_record(10.1, here=300, zero=4000, elsewhere=7988)])
    assert read("moe.zero_assignment_share", c) == pytest.approx(
        100 * 8096 / (2 * 12288))
    assert read("moe.real_held_share", c) == pytest.approx(
        100 * 556 / (556 + 7936 + 7988))
    # uniform routing over 768 outputs, 16 of the 512 experts held
    uniform = collected([decode_record(10.0)])
    assert read("moe.zero_assignment_share", uniform) == pytest.approx(
        100 / 3)
    assert read("moe.real_held_share", uniform) == pytest.approx(100 / 32)
    # a prefill between the bursts counts in neither
    steps = [decode_record(10.0), {
        "kind": "prefill", "ts": 10.3, "total_s": 0.05, "tokens": 700,
        "active_slots": 8, "experts_touched": 64, "expert_assignments": 9000,
        "assignments_elsewhere": 9000, "zero_assignments": 15600,
        "expert_load_max": 90}]
    assert read("moe.zero_assignment_share", collected(steps)
                ) == pytest.approx(100 / 3)


def test_trace_readers_on_hand_worked_numbers():
    steps = [decode_record(100.1),  # in the traced part
             decode_record(90.0, touched=160)]  # before it
    trace = {"wall_start": 99.0, "wall_stop": 107.0,
             "ops": {"grouped_expert_matmul_bf16_384_2048_":
                     {"time_s": 0.012, "count": 64},
                     "grouped_expert_matmul_f32_384_6144_":
                     {"time_s": 0.008, "count": 32},
                     "paged_latent_decode_bf16_32_64_512_":
                     {"time_s": 0.004, "count": 64},
                     "fusion_bf16_32_12288_": {"time_s": 9.0, "count": 1}},
             "modules": {"jit_many(123)": {"count": 8, "time_s": 0.8,
                                           "median_s": 0.1}}}
    c = collected(steps, trace)
    w = ROOFLINE.held_experts(CONFIG, experts_touched=200, assignments=256)
    want, bound = peaks.roofline_share_pct(w["flops"], w["bytes"], 0.020, V5E)
    assert read("kernel.shortcut_held_experts_roofline", c) == pytest.approx(
        want)
    assert 0 < want < 100 and bound == "memory"
    # 64 calls of the attention kernel: 8 sub-layers x 8 steps
    live = 32 * (100 + 512 * 47 / 60)
    w = ROOFLINE.latent_decode_call(CONFIG, live_tokens=live, rows=32)
    want, _ = peaks.roofline_share_pct(64 * w["flops"], 64 * w["bytes"],
                                       0.004, V5E)
    assert read("kernel.shortcut_latent_decode_roofline", c) == pytest.approx(
        want, rel=1e-3)
    assert 0 < want < 100
    # both records are the window's: (200 + 160) / 16 experts a step
    w = ROOFLINE.decode_step(CONFIG, c["engine"], live_tokens=live, rows=32,
                             experts_touched=(200 + 160) / 16)
    want, _ = peaks.roofline_share_pct(w["flops"], w["bytes"], 0.1 / 8, V5E)
    assert read("model.shortcut_moe_decode_roofline", c) == pytest.approx(
        want, rel=1e-3)
    assert 0 < want < 100


@pytest.mark.parametrize("name", READERS)
def test_readers_give_nothing_for_a_program_without_the_counters(name):
    """The parent commit, and every other family: step records without the
    zero-compute count, a trace without the kernels, another configuration.
    Nothing, and no exception."""
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
        "paged_latent_decode_bf16_32_32_512_": {"time_s": 1.0, "count": 10}},
        "modules": trace["modules"]}
    for other in ("kanana-2-30b-a3b-l8", "nemotron-3-nano-30b-a3b-l14"):
        c = {**collected(hybrid, full),
             "config": mf.load_config(MANIFEST, other)}
        assert read(name, c) is None, other


def test_the_cells_path_runs_end_to_end_on_the_cpu_at_a_ci_size():
    """`run.py --rehearse --trace 1` on the family through the real
    launcher, gateway and generator: `correct` with the routing heard
    (prefill, two extends, decode steps through the pages), every request
    served, the three assignment counters on the window's records and their
    readers in the line."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(mf.ROOT, "benchmark", "run.py"),
         "--manifest", REHEARSAL, "--workload", "tiny-shortcut.closed",
         "--seed", "2147483655", "--seconds", "2", "--trace", "1",
         "--rehearse"],
        env=env, capture_output=True, text=True, timeout=280, cwd=mf.ROOT)
    assert proc.returncode == 4, proc.stderr[-3000:]
    split, line = map(json.loads, proc.stdout.strip().splitlines()[-2:])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert split["correctness"]["grounds"] == []
    assert split["correctness"]["positions_compared"] == 1 + 2 + 6
    assert split["compiles_in_window"] == 0
    # 4 of 12 outputs zero-compute, the second half of 8 experts held
    assert 10 <= line["metrics"]["moe.zero_assignment_share"]["value"] <= 60
    assert 20 <= line["metrics"]["moe.real_held_share"]["value"] <= 80
    assert line["metrics"]["engine.programs_built_in_window"]["value"] == 0
    # device-trace readers find no device plane on the CPU: left out
    assert not set(line["metrics"]) & set(READERS[:3])
    with open(os.path.join(mf.ROOT, ".bench_run", "tiny-shortcut.closed",
                           "last_run.json")) as f:
        steps = json.load(f)["steps"]
    decodes = [r for r in steps if r["kind"] == "decode"]
    assert decodes
    for r in decodes:  # 2 layers x 3 a token
        assert (r["zero_assignments"] + r["expert_assignments"]
                + r["assignments_elsewhere"]) == r["tokens"] * 2 * 3


# --- benchmark/check_shortcut.py: the controls of the new layer --------------

def _shortcut(cases, capsys, monkeypatch, seed="5"):
    from benchmark import check_shortcut

    rehearsal = os.path.dirname(REHEARSAL)
    monkeypatch.setattr(sys, "argv", [
        "check_shortcut.py", "--config",
        os.path.join(rehearsal, "configs", "debug-longcat-tiny.json"),
        "--base", rehearsal, "--seeds", seed, "--cases", cases])
    monkeypatch.setattr(check_shortcut, "ROOT", tempfile.mkdtemp())  # its log
    monkeypatch.setenv("LLMLB_INIT_TIMEOUT", "0")  # no watchdog under capsys
    assert check_shortcut.main() == 0
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()
             if line.startswith("{")]
    return {line["case"]: line for line in lines}


def test_the_sound_program_passes_and_each_of_the_six_controls_is_refused(
        capsys, monkeypatch):
    got = _shortcut("program,unbiased_choice,zeroed_chosen_expert,"
                    "zero_dropped,shortcut_early,scales_off,int8_weights",
                    capsys, monkeypatch)
    sound = got["program"]["result"]
    assert sound["ok"] is True and sound["max_rel_rms_err"] < 1e-4
    assert sound["dropped_assignments"] == 0
    assert 0 < got["program"]["chosen_zero_share"] < 1
    assert 0 < got["program"]["chosen_held_share"] < 1
    for case in ("zeroed_chosen_expert", "zero_dropped", "shortcut_early",
                 "scales_off", "int8_weights"):
        result = got[case]["result"]
        assert result["ok"] is False, case
        assert "logits" in result["grounds"], case
    assert got["zeroed_chosen_expert"]["read_by"] > 0
    for case in ("zero_dropped", "shortcut_early", "scales_off"):
        assert got[case]["result"]["max_rel_rms_err"] > 0.05, case
    wrong = got["unbiased_choice"]["result"]
    assert "choice_is_own_topk" in wrong["grounds"]
    assert wrong["max_rel_rms_err"] < 1e-4


def test_the_precision_control_leaves_the_true_weights_behind(capsys,
                                                              monkeypatch):
    got = _shortcut("int8_weights,program", capsys, monkeypatch, seed="7")
    assert got["int8_weights"]["result"]["ok"] is False
    assert got["program"]["result"]["ok"] is True
    assert (got["program"]["result"]["max_rel_rms_err"] < 1e-4
            < got["int8_weights"]["result"]["max_rel_rms_err"])
