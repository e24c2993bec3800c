"""The hybrid state-space mixture's files in the benchmark (PR 38): its
configuration against the catalog row it was cut from, the operations and
bytes of benchmark/roofline/hybrid_moe.py and the five readers on
hand-worked numbers, what the readers give a program that has no such
counters (nothing), benchmark/check_hybrid.py and its controls at a CI size,
and the new cell's path end to end on the CPU (`run.py --rehearse`)."""

import json
import os
import subprocess
import sys
import tempfile

import pytest

from benchmark import manifest as mf
from benchmark import peaks

MANIFEST = mf.load()
NAME = "nemotron-3-nano-30b-a3b-l14"
CELL = NAME + ".decode-saturated"
CONFIG = mf.load_config(MANIFEST, NAME)
ROOFLINE = mf.load_module("roofline", "hybrid_moe")
HERE = os.path.dirname(os.path.abspath(__file__))
REHEARSAL = os.path.join(HERE, "rehearsal_hybrid", "BENCHMARK.json")
V5E = peaks.peaks_for("TPU v5 lite")
READERS = ("model.hybrid_decode_roofline", "kernel.ssm_decode_step_roofline",
           "kernel.held_experts_roofline", "moe.held_assignment_share",
           "ssm.state_bytes_share")
LAYER = {"model.hybrid_decode_roofline": "model step",
         "kernel.ssm_decode_step_roofline": "kernels",
         "kernel.held_experts_roofline": "kernels",
         "moe.held_assignment_share": "model step",
         "ssm.state_bytes_share": "model step"}

with open(os.path.join(mf.HERE, "settings.json")) as f:
    SETTINGS = json.load(f)

STATE = 64 * 64 * 128  # one sequence's recurrent state in one layer
EXPERT_BYTES = 2 * 2688 * 1856 * 2  # one routed expert's two matrices


def test_the_manifest_is_sound_and_the_cell_is_the_issues():
    assert mf.check(MANIFEST) == []
    cell = mf.cell(MANIFEST, CELL)
    assert cell == {**cell, "chips": 1, "config": NAME,
                    "traffic": "decode-saturated"}
    assert "1.5" in cell["why"] and "3.0" in cell["why"]
    assert len(cell["why"]) <= 200
    assert MANIFEST["workloads"][-1] == cell  # added at the end
    traffic = mf.load_traffic("decode-saturated")  # the file that was there
    assert {k: traffic[k] for k in (
        "generator", "clients", "prompt", "max_tokens", "ramp_s",
        "start_after_tokens", "requests_per_client")} == {
        "generator": "closed_loop", "clients": 32,
        "prompt": {"kind": "uniform", "lo": 64, "hi": 128},
        "max_tokens": 512, "ramp_s": 16, "start_after_tokens": 2,
        "requests_per_client": 8}
    per_layer = {m["name"]: m for m in MANIFEST["per_layer"]}
    assert [m["name"] for m in MANIFEST["per_layer"][-5:]] == list(READERS)
    for name in READERS:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == "tpot_p50_s"
        assert per_layer[name]["layer"] == LAYER[name]
        assert per_layer[name]["unit"] == "%"
    reported = {m["name"] for m in mf.metrics_for(MANIFEST, "per_layer", CELL)}
    assert set(READERS) | {"model.decode_step_s", "sched.host_share",
                           "device.idle_share", "device.hbm_peak_bytes",
                           "engine.compiles_in_window",
                           "engine.programs_built_in_window"} <= reported
    # the other families' model-step readers have nothing to read here
    assert not reported & {"model.decode_program_roofline",
                           "model.latent_moe_decode_roofline",
                           "model.block_pass_roofline",
                           "kernel.paged_latent_decode_roofline"}
    assert {m["name"] for m in mf.metrics_for(MANIFEST, "end_to_end", CELL)
            } == {"tpot_p50_s", "setup_s"}
    # no accepted metric's list was touched: the cell is on none of them
    for m in MANIFEST["per_layer"][:-5]:
        assert CELL not in m.get("workloads", [])


# nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16's config.json as the catalog
# (/opt/skills/guides/model-configs/architectures.jsonl) has it, carried here
# so that the test holds where the catalog is not installed.
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2,
    "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8,
    "n_routed_experts": 128, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072,
}
SOURCE = ("https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/"
          "blob/main/config.json")
CUTS = {"num_hidden_layers", "hybrid_override_pattern", "n_routed_experts"}


def test_the_configuration_holds_the_published_keys_and_three_cuts():
    """What `test_manifest.py::test_a_configuration_file_cuts_depth_only`
    means, against this model's own widths (that test asserts Mistral-7B's
    of every configuration and fails for this one as a new case: PERF.md
    section 7)."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):  # the copy above is the catalog's row
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
        assert (row["config"], row["source_url"]) == (PUBLISHED, SOURCE)
    differs = {k for k, v in PUBLISHED.items() if CONFIG.get(k, "-") != v}
    assert differs == CUTS == set(CONFIG["reduced"])
    entry = mf.config_entry(MANIFEST, NAME)
    assert entry["reduced"] == ["num_hidden_layers", "hybrid_override_pattern",
                                "n_routed_experts"]
    assert MANIFEST["configs"][-1] == entry
    assert entry["source"] == CONFIG["source"] == SOURCE
    # depth: the published pattern's first 14 characters, every kind in it
    pattern = CONFIG["hybrid_override_pattern"]
    assert pattern == PUBLISHED["hybrid_override_pattern"][:14]
    assert CONFIG["num_hidden_layers"] == len(pattern) == 14
    assert [pattern.count(k) for k in "M*E"] == [6, 2, 6]
    # the chip's share: half the experts, named in keys the class reads
    assert CONFIG["n_routed_experts"] == 64
    assert CONFIG["expert_parallel"] == {"chips": 2, "chip": 0, "experts": 128}
    for key in CUTS:
        cut = CONFIG["reduced"][key]
        assert (cut["published"], cut["here"]) == (PUBLISHED[key], CONFIG[key])
    assert CONFIG["torch_dtype"] == "bfloat16"
    assert set(CONFIG["assumed"]) == {
        "rotary_embedding", "ssm_state_dtype", "e_score_correction_bias",
        "weights", "time_step_limit", "balance"}
    assert "2 v5e chips" in CONFIG["deployment"]
    assert "1.5 assignments" in CONFIG["deployment"]
    correctness = CONFIG["correctness"]
    assert correctness["reference"] == "nemotron_h"
    # an extend from a scan-chunk boundary (256) and one from inside (320)
    assert (correctness["prefill_tokens"], correctness["extend_chunks"],
            correctness["extend_tokens"], correctness["decode_steps"]) == (
        256, 2, 64, 16)
    assert correctness["prefill_tokens"] % CONFIG["chunk_size"] == 0
    assert (correctness["prefill_tokens"] + correctness["extend_tokens"]
            ) % CONFIG["chunk_size"] != 0
    assert "TO BE SET" not in correctness["why"]
    engine = CONFIG["engine"]
    assert (engine["num_slots"], engine["slot_capacity"],
            engine["kv_page_size"], engine["kv_pages"], engine["decode_burst"],
            engine["prefix_cache"]) == (32, 2048, 128, 544, 8, False)
    assert engine["prefill_buckets"] == [32, 64, 128, 256, 512]


def test_the_program_reads_the_configuration_as_a_hybrid_with_a_share():
    from benchmark import launcher
    from llmlb_tpu.engine.scheduler import kv_page_bytes
    from llmlb_tpu.models import family_for, nemotron_h

    cfg = launcher.build_cfg(CONFIG)
    assert family_for(cfg) is nemotron_h
    assert (cfg.num_layers, cfg.pattern, cfg.router_experts, cfg.held_experts,
            cfg.experts_per_token, cfg.moe_intermediate_size,
            cfg.shared_intermediate_size, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim_, cfg.vocab_size) == (
        14, "MEMEM*EMEMEM*E", 128, (0, 64), 6, 1856, 3712, 32, 2, 128, 131072)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state,
            cfg.conv_kernel, cfg.chunk_size, cfg.d_inner, cfg.conv_dim) == (
        64, 64, 8, 128, 4, 128, 4096, 6144)
    # a page of the pool, a slot's state and the weights, as the file's
    # arithmetic has them
    assert kv_page_bytes(cfg, 128) == 2 * 128 * 2 * 2 * 128 * 2
    assert nemotron_h.state_slot_bytes(cfg) == 6 * (STATE * 4 + 3 * 6144 * 2)
    import jax

    shapes = jax.eval_shape(lambda k: nemotron_h.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    n_params = sum(v.size for v in shapes.values())
    assert abs(n_params * 2 / 1e9 - 9.87) < 0.01
    assert shapes["we_up"].shape == (6, 64, 1856, 2688)
    assert shapes["router"].shape == (6, 2688, 128)


def test_the_reference_is_found_by_the_configurations_name():
    from benchmark import reference

    module = reference.module_for(CONFIG)
    assert module.__name__.endswith("nemotron_h")
    assert module.FOLLOWS == "routing"
    assert module.held_range(CONFIG) == (0, 64)
    assert module.held_range({**CONFIG, "expert_parallel": {
        "chips": 2, "chip": 1, "experts": 128}}) == (64, 64)
    with open(module.__file__) as f:
        source = f.read()
    # independent of the program's ops
    assert "llmlb_tpu" not in source.replace("llmlb_tpu/", "")


def test_roofline_accounts_on_hand_worked_numbers():
    assert ROOFLINE.state_elements(CONFIG) == STATE
    assert ROOFLINE.expert_bytes(CONFIG) == EXPERT_BYTES
    assert [ROOFLINE.layers(CONFIG, k) for k in "M*E"] == [6, 2, 6]
    # one layer's state step for 32 rows: the state read and written in
    # float32, x, z-less inputs (x, y of 4,096, dt of 64, B and C of 1,024)
    w = ROOFLINE.ssm_step_call(CONFIG, rows=32)
    assert w["bytes"] == 32 * (2 * STATE * 4 + (2 * 4096 + 64 + 2 * 1024) * 2)
    assert w["flops"] == 6 * 32 * STATE
    assert 0.16e-3 < w["bytes"] / V5E["hbm_bytes_per_s"] < 0.17e-3
    # grouped products: 300 experts touched by 600 assignments
    w = ROOFLINE.held_experts(CONFIG, experts_touched=300, assignments=600)
    assert w["flops"] == 600 * 2 * 2 * 2688 * 1856
    assert w["bytes"] == 300 * EXPERT_BYTES + 600 * 2 * (2688 + 1856) * 2
    # a step of 32 rows that touches 300 of the 6 x 64 = 384 experts held
    engine = {"param_bytes": 9_874_000_000, "n_params": 4_937_000_000}
    w = ROOFLINE.decode_step(CONFIG, engine, live_tokens=12_000, rows=32,
                             experts_touched=300)
    embed = 131072 * 2688
    state = 32 * 6 * (2 * STATE * 4 + 2 * 3 * 6144 * 2)
    assert w["state_bytes"] == state
    assert w["bytes"] == (9_874_000_000 - embed * 2 - 84 * EXPERT_BYTES
                          + state + 12_000 * 2 * 2 * 2 * 128 * 2)
    per_expert = 2 * 2688 * 1856
    active = (4_937_000_000 - embed - 384 * per_expert
              + 6 * 6 * 0.5 * per_expert)  # half a token's six are held
    assert w["flops"] == (2 * active * 32 + 4 * 12_000 * 32 * 128 * 2
                          + 6 * 32 * 6 * STATE)
    # the issue's arithmetic: about 8.3 GB a step, the state about a tenth
    assert 7.9e9 < w["bytes"] < 8.7e9
    assert 0.09 < w["state_bytes"] / w["bytes"] < 0.11
    # no kernel name here may be taken for another family's
    assert not any(n.startswith(("paged_flash_decode", "paged_latent_decode",
                                 "paged_flash_extend"))
                   for n in ROOFLINE.SSM_STEP_OPS + ROOFLINE.ROUTED_EXPERT_OPS)


def decode_record(ts, *, rows=32, burst=8, touched=2400, here=4600,
                  elsewhere=4616):
    return {"kind": "decode", "ts": ts, "total_s": 0.1, "active_slots": rows,
            "tokens": rows * burst, "experts_touched": touched,
            "expert_assignments": here, "assignments_elsewhere": elsewhere,
            "expert_load_max": 12, "state_rows": rows * burst}


def collected(steps, trace=None):
    reqs = [{"first_s": 0.0, "last_s": 60.0, "prompt_tokens": 100,
             "words": 512} for _ in range(32)]
    return {"config": CONFIG, "steps": steps, "trace": trace, "peaks": V5E,
            "seconds": 51, "settings": SETTINGS, "requests": reqs,
            "engine": {"decode_burst": 8, "param_bytes": 9_874_000_000,
                       "n_params": 4_937_000_000}}


def read(name, c):
    return mf.load_module("layer_metrics", name).read(c)


def test_counter_readers_on_hand_worked_numbers():
    c = collected([decode_record(10.0), decode_record(10.1, here=4700,
                                                      elsewhere=4516)])
    assert read("moe.held_assignment_share", c) == pytest.approx(
        100 * 9300 / (9300 + 9132))
    # the whole window: every request holds 100 + 512 x t/60 tokens
    live = 32 * (100 + 512 * 25.5 / 60)
    w = ROOFLINE.decode_step(CONFIG, c["engine"], live_tokens=live, rows=32,
                             experts_touched=2400 / 8)
    assert read("ssm.state_bytes_share", c) == pytest.approx(
        100 * w["state_bytes"] / w["bytes"], rel=1e-3)
    assert 9 < read("ssm.state_bytes_share", c) < 11
    # half the rows live: half the state, the same weights
    half = collected([decode_record(10.0, rows=16, touched=1500)])
    assert read("ssm.state_bytes_share", half) < read(
        "ssm.state_bytes_share", c)
    # a prefill between the bursts counts in neither
    steps = [decode_record(10.0), {
        "kind": "prefill", "ts": 10.3, "total_s": 0.05, "tokens": 700,
        "active_slots": 8, "experts_touched": 384, "expert_assignments": 2100,
        "assignments_elsewhere": 2100, "expert_load_max": 90,
        "state_rows": 8, "scan_tokens": 700, "scan_chunks": 8}]
    assert read("moe.held_assignment_share", collected(steps)) == pytest.approx(
        100 * 4600 / 9216)


def test_trace_readers_on_hand_worked_numbers():
    steps = [decode_record(100.1),  # in the traced part
             decode_record(90.0, touched=2000)]  # before it
    trace = {"wall_start": 99.0, "wall_stop": 107.0,
             "ops": {"grouped_expert_matmul_bf16_192_1856_": {"time_s": 0.035, "count": 48},
                     "grouped_expert_matmul_f32_192_2688_": {"time_s": 0.035, "count": 48},
                     "ssm_decode_step_f32_32_8_4096_": {"time_s": 0.0125, "count": 48},
                     "paged_flash_decode_bf16_32_2_16_128_": {"time_s": 9.0, "count": 1},
                     "fusion_f32_6_32_64_64_128_": {"time_s": 9.0, "count": 1}},
             "modules": {"jit_many(123)": {"count": 8, "time_s": 0.8,
                                           "median_s": 0.1}}}
    c = collected(steps, trace)
    w = ROOFLINE.held_experts(CONFIG, experts_touched=2400,
                              assignments=4600)
    want, bound = peaks.roofline_share_pct(w["flops"], w["bytes"], 0.07, V5E)
    assert read("kernel.held_experts_roofline", c) == pytest.approx(want)
    assert 0 < want < 100 and bound == "memory"
    # 256 rows advanced in each of the 6 state-space layers
    w = ROOFLINE.ssm_step_call(CONFIG, rows=256 * 6)
    want, bound = peaks.roofline_share_pct(w["flops"], w["bytes"], 0.0125, V5E)
    assert read("kernel.ssm_decode_step_roofline", c) == pytest.approx(want)
    assert 0 < want < 100 and bound == "memory"
    # both records are the window's: (2400 + 2000) / 16 experts a step
    live = 32 * (100 + 512 * 47 / 60)
    w = ROOFLINE.decode_step(CONFIG, c["engine"], live_tokens=live, rows=32,
                             experts_touched=(2400 + 2000) / 16)
    want, _ = peaks.roofline_share_pct(w["flops"], w["bytes"], 0.1 / 8, V5E)
    assert read("model.hybrid_decode_roofline", c) == pytest.approx(
        want, rel=1e-3)
    assert 0 < want < 100


@pytest.mark.parametrize("name", READERS)
def test_readers_give_nothing_for_a_program_without_the_counters(name):
    """The parent commit, and every other family: step records without the
    state's and the share's counts, a trace without the kernels, another
    configuration. Nothing, and no exception."""
    plain = [{"kind": "decode", "ts": 100.0, "total_s": 0.2, "tokens": 256,
              "active_slots": 32}]
    latent = [{**plain[0], "experts_touched": 900, "expert_assignments": 1500,
               "expert_load_max": 9}]
    trace = {"wall_start": 99.0, "wall_stop": 107.0,
             "ops": {"paged_flash_decode_bf16_32_8_4_128_":
                     {"time_s": 1.0, "count": 10},
                     "grouped_expert_matmul_bf16_384_768_":
                     {"time_s": 1.0, "count": 10}},
             "modules": {"jit_many(1)": {"count": 8, "time_s": 1.6,
                                         "median_s": 0.2}}}
    assert read(name, collected(plain, trace)) is None
    assert read(name, collected([], None)) is None
    other = {**collected(latent, trace),
             "config": mf.load_config(MANIFEST, "kanana-2-30b-a3b-l8")}
    assert read(name, other) is None


def test_the_cells_path_runs_end_to_end_on_the_cpu_at_a_ci_size():
    """`run.py --rehearse --trace 1` on the hybrid family through the real
    launcher, gateway and generator: `correct` with the routing heard
    (prefill, an extend from inside a scan chunk, decode steps through the
    state pool), every request served, the share's and the state's counters
    on the window's records and their readers in the line."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(mf.ROOT, "benchmark", "run.py"),
         "--manifest", REHEARSAL, "--workload", "tiny-hybrid.closed", "--seed",
         "2147483655", "--seconds", "2", "--trace", "1", "--rehearse"],
        env=env, capture_output=True, text=True, timeout=280, cwd=mf.ROOT)
    assert proc.returncode == 4, proc.stderr[-3000:]
    split, line = map(json.loads, proc.stdout.strip().splitlines()[-2:])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert split["correctness"]["grounds"] == []
    assert split["correctness"]["positions_compared"] == 1 + 2 + 6
    assert split["compiles_in_window"] == 0
    # the second half of 8 experts under seeded routing: near a half
    assert 25 <= line["metrics"]["moe.held_assignment_share"]["value"] <= 75
    assert 0 < line["metrics"]["ssm.state_bytes_share"]["value"] < 100
    assert line["metrics"]["engine.programs_built_in_window"]["value"] == 0
    # device-trace readers find no device plane on the CPU: left out
    assert not set(line["metrics"]) & set(READERS[:3])
    with open(os.path.join(mf.ROOT, ".bench_run", "tiny-hybrid.closed",
                           "last_run.json")) as f:
        steps = json.load(f)["steps"]
    decodes = [r for r in steps if r["kind"] == "decode"]
    assert decodes and all(r["state_rows"] == r["tokens"] for r in decodes)
    assert any("scan_tokens" in r for r in steps if r["kind"] == "prefill")


# --- benchmark/check_hybrid.py: the controls of a state per slot -------------

def _hybrid(cases, capsys, monkeypatch, seed="5"):
    from benchmark import check_hybrid

    rehearsal = os.path.dirname(REHEARSAL)
    monkeypatch.setattr(sys, "argv", [
        "check_hybrid.py", "--config",
        os.path.join(rehearsal, "configs", "debug-nemotron-h-tiny.json"),
        "--base", rehearsal, "--seeds", seed, "--cases", cases])
    monkeypatch.setattr(check_hybrid, "ROOT", tempfile.mkdtemp())  # its log
    monkeypatch.setenv("LLMLB_INIT_TIMEOUT", "0")  # no watchdog under capsys
    assert check_hybrid.main() == 0
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()
             if line.startswith("{")]
    return {line["case"]: line["result"] for line in lines}


def test_the_sound_variants_pass_and_each_control_of_the_state_is_refused(
        capsys, monkeypatch):
    got = _hybrid("program,interleaved_decode,live_mask_off,no_decay,"
                  "conv_not_carried,unbiased_choice,zeroed_chosen_expert",
                  capsys, monkeypatch)
    sound = got["program"]
    assert sound["ok"] is True and sound["max_rel_rms_err"] < 1e-4
    assert sound["dropped_assignments"] == 0
    # a decode step over the prefilling row with `live` false moves nothing
    masked = got["interleaved_decode"]
    assert masked["ok"] is True
    assert masked["max_rel_rms_err"] == sound["max_rel_rms_err"]
    for case in ("live_mask_off", "no_decay", "conv_not_carried",
                 "zeroed_chosen_expert"):
        assert got[case]["ok"] is False, case
        assert set(got[case]["grounds"]) & {"logits", "router_rel_rms_err"}
    assert got["no_decay"]["max_rel_rms_err"] > 0.05
    wrong = got["unbiased_choice"]
    assert "choice_is_own_topk" in wrong["grounds"]
    assert wrong["max_rel_rms_err"] < 1e-4


def test_the_precision_and_state_controls_at_a_ci_size(capsys, monkeypatch):
    """`int8_weights` is refused and leaves the true weights behind for the
    reference; `state_bf16` runs (in float32 on the CPU it reads the
    rounding of the state alone, above the sound program's and under the
    matrices')."""
    got = _hybrid("int8_weights,state_bf16,program", capsys, monkeypatch,
                  seed="7")
    assert got["int8_weights"]["ok"] is False
    assert "logits" in got["int8_weights"]["grounds"]
    assert got["program"]["ok"] is True
    assert got["program"]["max_rel_rms_err"] < 1e-4
    assert (got["program"]["max_rel_rms_err"]
            < got["state_bf16"]["max_rel_rms_err"]
            < got["int8_weights"]["max_rel_rms_err"])
