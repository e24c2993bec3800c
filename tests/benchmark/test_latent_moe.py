"""The latent mixture's files in the benchmark: its configuration against the
catalog row it was cut from, the operations and bytes of
benchmark/roofline/latent_moe.py and the five readers on hand-worked
numbers, what the readers give a program that has no counters (nothing),
and the new cell's path end to end on the CPU at a CI size."""

import json
import os
import subprocess
import tempfile
import sys

import pytest

from benchmark import manifest as mf
from benchmark import moe_counters, peaks

MANIFEST = mf.load()
CELL = "kanana-2-30b-a3b-l8.decode-wide"
CONFIG = mf.load_config(MANIFEST, "kanana-2-30b-a3b-l8")
ROOFLINE = mf.load_module("roofline", "latent_moe")
HERE = os.path.dirname(os.path.abspath(__file__))
REHEARSAL = os.path.join(HERE, "rehearsal_latent", "BENCHMARK.json")
V5E = peaks.peaks_for("TPU v5 lite")

with open(os.path.join(mf.HERE, "settings.json")) as f:
    SETTINGS = json.load(f)

EXPERT_BYTES = 3 * 2048 * 768 * 2  # one routed expert's three matrices


def test_the_manifest_is_sound_and_the_cell_is_the_issues():
    assert mf.check(MANIFEST) == []
    cell = mf.cell(MANIFEST, CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "decode-wide"
    traffic = mf.load_traffic("decode-wide")
    assert {k: traffic[k] for k in (
        "generator", "clients", "prompt", "max_tokens", "start_after_tokens",
        "requests_per_client", "max_prefill_group")} == {
        "generator": "closed_loop", "clients": 64,
        "prompt": {"kind": "uniform", "lo": 64, "hi": 128},
        "max_tokens": 1024, "start_after_tokens": 2,
        "requests_per_client": 8, "max_prefill_group": 8}
    assert traffic["ramp_s"] >= 40
    reported = {m["name"] for m in mf.metrics_for(MANIFEST, "per_layer", CELL)}
    assert {"kernel.paged_latent_decode_roofline",
            "kernel.routed_experts_roofline",
            "model.latent_moe_decode_roofline", "moe.experts_touched_share",
            "moe.load_max_over_mean", "model.decode_step_s",
            "sched.host_share", "device.idle_share"} <= reported
    # the GQA kernel's reader has nothing to read here, and the dense
    # step's roofline counts K and V per head and every expert as read: it
    # read 104.9% in this cell (PERF.md section 6, PR 31)
    assert not reported & {"kernel.paged_flash_decode_roofline",
                           "model.decode_program_roofline"}
    assert {m["name"] for m in mf.metrics_for(MANIFEST, "end_to_end", CELL)
            } == {"tpot_p50_s", "setup_s"}


# kakaocorp/kanana-2-30b-a3b-instruct-2601's config.json as the catalog
# (/opt/skills/guides/model-configs/architectures.jsonl) has it, carried here
# so that the test holds where the catalog is not installed.
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 2, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 48,
    "num_key_value_heads": 32, "q_lora_rank": None, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 1000000,
    "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 128256,
}
SOURCE = ("https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601/"
          "blob/main/config.json")


def test_the_configuration_holds_the_published_keys_and_one_cut():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):  # the copy above is the catalog's row
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "kanana-2-30b-a3b-instruct-2601")
        assert (row["config"], row["source_url"]) == (PUBLISHED, SOURCE)
    differs = {k for k, v in PUBLISHED.items() if CONFIG.get(k, "-") != v}
    assert differs == {"num_hidden_layers"} == set(CONFIG["reduced"])
    assert CONFIG["num_hidden_layers"] == 8 and CONFIG["source"] == SOURCE
    assert CONFIG["torch_dtype"] == "bfloat16"
    assert CONFIG["correctness"]["reference"] == "deepseek_v3"
    # `correct` goes through the extend path too, behind a prefilled prefix
    assert CONFIG["correctness"]["extend_chunks"] >= 2
    assert CONFIG["engine"]["kv_page_size"] == 128


def test_the_program_reads_the_configuration_as_a_latent_mixture():
    from benchmark import launcher
    from llmlb_tpu.models import deepseek_v3, family_for

    cfg = launcher.build_cfg(CONFIG)
    assert family_for(cfg) is deepseek_v3
    assert (cfg.num_layers, cfg.first_k_dense, cfg.num_experts,
            cfg.experts_per_token, cfg.kv_lora_rank) == (8, 1, 128, 6, 512)
    # one page of the pool, as the file's arithmetic has it
    from llmlb_tpu.engine.scheduler import kv_page_bytes

    assert kv_page_bytes(cfg, 128) == 8 * 128 * (512 + 128) * 2


def test_roofline_accounts_on_hand_worked_numbers():
    # one call of the kernel: 1,000 live tokens, 10 rows
    w = ROOFLINE.latent_decode_call(CONFIG, live_tokens=1000, rows=10)
    assert w["bytes"] == 1000 * 576 * 2 + 10 * 32 * (512 + 512 + 64) * 2
    assert w["flops"] == 1000 * 2 * 32 * (576 + 512)
    # grouped products: 100 experts touched by 300 assignments
    w = ROOFLINE.routed_experts(CONFIG, experts_touched=100, assignments=300)
    assert w["flops"] == 300 * 3 * 2 * 2048 * 768
    assert w["bytes"] == 100 * EXPERT_BYTES + 300 * (2 * 2048 + 3 * 768) * 2
    # a decode step that touches 800 of the 7 x 128 = 896 experts it holds
    engine = {"param_bytes": 10_140_000_000, "n_params": 5_070_000_000}
    w = ROOFLINE.decode_step(CONFIG, engine, live_tokens=40_000, rows=64,
                             experts_touched=800)
    embed = 128256 * 2048
    assert w["bytes"] == (10_140_000_000 - embed * 2 - 96 * EXPERT_BYTES
                          + 40_000 * 8 * 576 * 2)
    active = 5_070_000_000 - embed - (896 - 7 * 6) * 3 * 2048 * 768
    assert w["flops"] == 2 * active * 64 + 8 * 40_000 * 2 * 32 * (576 + 512)
    assert ROOFLINE.expert_layers(CONFIG) == 7
    # no kernel name here may be taken for the GQA kernel's
    assert not any(n.startswith("paged_flash_decode")
                   for n in ROOFLINE.LATENT_DECODE_OPS + ROOFLINE.ROUTED_EXPERT_OPS)


def decode_record(ts, touched, assignments, load_max, slots=64, k=8):
    return {"kind": "decode", "ts": ts, "total_s": 0.2, "tokens": slots * k,
            "active_slots": slots, "experts_touched": touched,
            "expert_assignments": assignments, "expert_load_max": load_max}


def collected(steps, trace=None):
    reqs = [{"first_s": 0.0, "last_s": 60.0, "prompt_tokens": 100,
             "words": 1000} for _ in range(64)]
    return {"config": CONFIG, "steps": steps, "trace": trace, "peaks": V5E,
            "seconds": 51, "settings": SETTINGS, "requests": reqs,
            "engine": {"decode_burst": 8, "param_bytes": 10_140_000_000,
                       "n_params": 5_070_000_000}}


def read(name, c):
    return mf.load_module("layer_metrics", name).read(c)


def test_counter_readers_on_hand_worked_numbers():
    # two bursts of 8 steps over 7 layers: 6,272 and 6,720 of 7,168 slots
    steps = [decode_record(10.0, 6272, 64 * 6 * 7 * 8, 9),
             decode_record(10.2, 6720, 64 * 6 * 7 * 8, 12),
             {"kind": "prefill", "ts": 10.3, "total_s": 0.05, "tokens": 700,
              "active_slots": 8, "experts_touched": 800,
              "expert_assignments": 700 * 6 * 7, "expert_load_max": 60}]
    c = collected(steps)
    assert moe_counters.touched_per_step(c) == (6272 + 6720) / 16
    assert read("moe.experts_touched_share", c) == pytest.approx(
        100 * (6272 + 6720) / 16 / 896)
    # the mean expert takes 64 x 6 / 128 = 3 assignments a step and layer
    assert read("moe.load_max_over_mean", c) == pytest.approx((3 + 4) / 2)


def test_trace_readers_on_hand_worked_numbers():
    steps = [decode_record(100.1, 6400, 64 * 6 * 7 * 8, 9),  # in the trace
             decode_record(90.0, 6400, 64 * 6 * 7 * 8, 9)]  # before it
    trace = {"wall_start": 99.0, "wall_stop": 107.0,
             "ops": {"ragged-dot-none_f32_384_768_": {"time_s": 0.05, "count": 112},
                     "ragged-dot-none_bf16_384_2048_": {"time_s": 0.03, "count": 56},
                     "ragged-dot-metadata_s32_129_": {"time_s": 0.001, "count": 56},
                     "paged_latent_decode_bf16_64_32_512_": {"time_s": 0.02, "count": 64},
                     "paged_flash_decode_bf16_32_8_4_128_": {"time_s": 9.0, "count": 1},
                     "fusion_bf16_64_2048_": {"time_s": 9.0, "count": 1}},
             "modules": {"jit_many(123)": {"count": 8, "time_s": 1.6,
                                           "median_s": 0.2}}}
    c = collected(steps, trace)
    assert len(moe_counters.traced(c)) == 1
    w = ROOFLINE.routed_experts(CONFIG, experts_touched=6400,
                                assignments=64 * 6 * 7 * 8)
    want, _ = peaks.roofline_share_pct(w["flops"], w["bytes"], 0.081, V5E)
    assert read("kernel.routed_experts_roofline", c) == pytest.approx(want)
    assert 0 < want < 100
    # every request holds 100 + 1000 x t/60 tokens; 64 rows decode
    live = 64 * (100 + 1000 * 47 / 60)
    w = ROOFLINE.latent_decode_call(CONFIG, live_tokens=live, rows=64)
    want, _ = peaks.roofline_share_pct(w["flops"] * 64, w["bytes"] * 64, 0.02, V5E)
    assert read("kernel.paged_latent_decode_roofline", c) == pytest.approx(want, rel=1e-3)
    w = ROOFLINE.decode_step(CONFIG, c["engine"], live_tokens=live, rows=64,
                             experts_touched=6400 / 8)
    want, _ = peaks.roofline_share_pct(w["flops"], w["bytes"], 0.2 / 8, V5E)
    assert read("model.latent_moe_decode_roofline", c) == pytest.approx(want, rel=1e-3)
    assert 0 < want < 100


@pytest.mark.parametrize("name", [
    "kernel.paged_latent_decode_roofline", "kernel.routed_experts_roofline",
    "model.latent_moe_decode_roofline", "moe.experts_touched_share",
    "moe.load_max_over_mean"])
def test_readers_give_nothing_for_a_program_without_the_counters(name):
    """The parent commit, and every dense model: step records without the
    counters, a trace without the kernels. Nothing, and no exception."""
    plain = [{"kind": "decode", "ts": 100.0, "total_s": 0.2, "tokens": 256,
              "active_slots": 32}]
    trace = {"wall_start": 99.0, "wall_stop": 107.0,
             "ops": {"paged_flash_decode_bf16_32_8_4_128_":
                     {"time_s": 1.0, "count": 10}},
             "modules": {"jit_many(1)": {"count": 8, "time_s": 1.6,
                                         "median_s": 0.2}}}
    assert read(name, collected(plain, trace)) is None
    assert read(name, collected([], None)) is None


def test_the_cells_path_runs_end_to_end_on_the_cpu_at_a_ci_size():
    """`run.py --rehearse --trace 1` on the latent family through the real
    launcher, gateway and generator: `correct` with the routing heard, the
    counters on the window's records, the counter readers in the line."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(mf.ROOT, "benchmark", "run.py"),
         "--manifest", REHEARSAL, "--workload", "tiny-mla.wide", "--seed",
         "2147483655", "--seconds", "2", "--trace", "1", "--rehearse"],
        env=env, capture_output=True, text=True, timeout=280, cwd=mf.ROOT)
    assert proc.returncode == 4, proc.stderr[-3000:]
    split, line = map(json.loads, proc.stdout.strip().splitlines()[-2:])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert split["correctness"]["grounds"] == []
    assert split["correctness"]["dropped_assignments"] == 0
    assert 0 < line["metrics"]["moe.experts_touched_share"]["value"] <= 100
    assert line["metrics"]["moe.load_max_over_mean"]["value"] >= 1
    # device-trace readers find no device plane on the CPU: left out
    assert "kernel.routed_experts_roofline" not in line["metrics"]


# --- benchmark/check_limits.py: the controls the limits are set between -----

def _limits(cases, capsys, monkeypatch, seed="5"):
    from benchmark import check_limits

    rehearsal = os.path.dirname(REHEARSAL)
    monkeypatch.setattr(sys, "argv", [
        "check_limits.py", "--config",
        os.path.join(rehearsal, "configs", "debug-mla-tiny.json"), "--base",
        rehearsal, "--seeds", seed, "--cases", cases])
    monkeypatch.setattr(check_limits, "ROOT", tempfile.mkdtemp())  # its log
    monkeypatch.setenv("LLMLB_INIT_TIMEOUT", "0")  # no watchdog under capsys
    assert check_limits.main() == 0
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()
             if line.startswith("{")]
    return {line["case"]: line for line in lines}


def test_the_precision_control_fails_the_logits_and_a_sound_program_passes(
        capsys, monkeypatch):
    """int8 weights in the program's place, the reference on the weights
    made again from the seed: float32 reads rounding only, int8 a few
    per cent, and the weights are the true ones again afterwards."""
    got = _limits("program,int8_weights,program", capsys, monkeypatch)
    assert got["program"]["result"]["ok"] is True  # the one after the control
    assert got["program"]["result"]["max_rel_rms_err"] < 1e-4
    control = got["int8_weights"]["result"]
    assert control["ok"] is False and "logits" in control["grounds"]
    assert 0.01 < control["max_rel_rms_err"] < 0.3
    assert control["dropped_assignments"] == 0 and control["choice_is_own_topk"]


def test_the_wrong_choice_control_fails_by_its_flips_with_sound_logits(
        capsys, monkeypatch):
    """Chosen by score alone, score + bias reported: the logits agree (the
    reference follows the choice), the scores agree, and the choice is
    refused on two grounds, neither of them the logits."""
    got = _limits("program,unbiased_choice", capsys, monkeypatch)
    control = got["unbiased_choice"]["result"]
    assert control["ok"] is False
    assert set(control["grounds"]) == {"choice_is_own_topk",
                                       "flips_at_wide_margin"}
    assert control["max_rel_rms_err"] < 1e-4
    assert control["router_rel_rms_err"] < 1e-4 and control["flips"] > 0
    assert control["widest_flip_margin"] > control["flip_margin_multiple"]


def test_the_zeroed_expert_is_one_the_compared_positions_read(
        capsys, monkeypatch):
    got = _limits("program,zeroed_chosen_expert", capsys, monkeypatch)
    line = got["zeroed_chosen_expert"]
    assert line["zeroed"][0] == 0 and line["read_by"] >= 1
    assert line["result"]["ok"] is False
    assert "logits" in line["result"]["grounds"]


def test_the_zeroed_chosen_expert_needs_the_programs_choices_first(
        capsys, monkeypatch):
    with pytest.raises(SystemExit, match="run `program` first"):
        _limits("zeroed_chosen_expert", capsys, monkeypatch)


def test_int8_rounding_takes_the_matrices_and_leaves_the_vectors():
    import jax.numpy as jnp
    import numpy as np

    from benchmark import check_limits

    w = np.linspace(-1.0, 1.0, 2 * 6 * 4, dtype=np.float32).reshape(2, 6, 4)
    params = {"wq": jnp.asarray(w), "dense_wq": jnp.asarray(w),
              "ln_attn": jnp.ones((2, 6)) * 0.3,
              "dense_ln_mlp": jnp.ones((2, 6)) * 0.3,
              "router_bias": jnp.ones((2, 4)) * 0.01234,
              "embed": jnp.asarray(w[0])}
    check_limits.rounded_to_int8(params)
    for name in ("ln_attn", "dense_ln_mlp"):
        assert float(abs(params[name] - 0.3).max()) == 0
    assert float(abs(params["router_bias"] - 0.01234).max()) == 0
    for name in ("wq", "dense_wq", "embed"):
        got = np.asarray(params[name])
        want = w if name != "embed" else w[0]
        step = abs(want).max(axis=-2, keepdims=True) / 127  # per out channel
        assert 0 < abs(got - want).max()
        assert (abs(got - want) <= step / 2 + 1e-7).all()


def test_compared_positions_are_the_checks_own():
    from benchmark import check_limits

    assert check_limits.compared_positions(
        {"prefill_tokens": 256, "extend_chunks": 2, "extend_tokens": 64,
         "decode_steps": 3}) == [255, 319, 383, 384, 385, 386]
    assert check_limits.compared_positions(
        {"prefill_tokens": 16, "decode_steps": 2}) == [15, 16, 17]
