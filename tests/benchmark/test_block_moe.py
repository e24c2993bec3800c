"""The block-diffusion mixture's files in the benchmark (PR 34): its
configuration against the catalog row it was cut from, the operations and
bytes of benchmark/roofline/block_moe.py and the four readers on hand-worked
numbers, what the readers give a program that has no such counters
(nothing), benchmark/check_blocks.py and its controls at a CI size, and the
new cell's path end to end on the CPU (`run.py --rehearse`)."""

import json
import os
import subprocess
import sys
import tempfile

import pytest

from benchmark import manifest as mf
from benchmark import peaks

MANIFEST = mf.load()
CELL = "sdar-30b-a3b-l7.decode-saturated"
CONFIG = mf.load_config(MANIFEST, "sdar-30b-a3b-l7")
ROOFLINE = mf.load_module("roofline", "block_moe")
HERE = os.path.dirname(os.path.abspath(__file__))
REHEARSAL = os.path.join(HERE, "rehearsal_blocks", "BENCHMARK.json")
V5E = peaks.peaks_for("TPU v5 lite")
READERS = ("diffusion.passes_per_token", "model.block_pass_roofline",
           "kernel.block_extend_roofline",
           "kernel.block_routed_experts_roofline")

with open(os.path.join(mf.HERE, "settings.json")) as f:
    SETTINGS = json.load(f)

EXPERT_BYTES = 3 * 2048 * 768 * 2  # one routed expert's three matrices


def test_the_manifest_is_sound_and_the_cell_is_the_issues():
    assert mf.check(MANIFEST) == []
    cell = mf.cell(MANIFEST, CELL)
    assert cell == {**cell, "chips": 1, "config": "sdar-30b-a3b-l7",
                    "traffic": "decode-saturated"}
    assert "1.25" in cell["why"] and len(cell["why"]) <= 200
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
    reported = {m["name"] for m in mf.metrics_for(MANIFEST, "per_layer", CELL)}
    assert set(READERS) | {"model.decode_step_s", "sched.host_share",
                           "device.idle_share", "device.hbm_peak_bytes",
                           "engine.compiles_in_window",
                           "engine.programs_built_in_window"} <= reported
    # the other families' kernel readers have nothing to read here
    assert not reported & {"kernel.paged_flash_decode_roofline",
                           "kernel.paged_latent_decode_roofline",
                           "model.decode_program_roofline",
                           "model.latent_moe_decode_roofline"}
    assert {m["name"] for m in mf.metrics_for(MANIFEST, "end_to_end", CELL)
            } == {"tpot_p50_s", "setup_s"}


# JetLM/SDAR-30B-A3B-Chat's config.json as the catalog
# (/opt/skills/guides/model-configs/architectures.jsonl) has it, carried here
# so that the test holds where the catalog is not installed.
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936,
}
SOURCE = "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json"


def test_the_configuration_holds_the_published_keys_and_one_cut():
    """What `test_manifest.py::test_a_configuration_file_cuts_depth_only`
    means, against this model's own widths (that test asserts Mistral-7B's
    of every configuration and fails for this one as a new case:
    tests/benchmark/conftest.py, PERF.md section 7)."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):  # the copy above is the catalog's row
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "SDAR-30B-A3B-Chat")
        assert (row["config"], row["source_url"]) == (PUBLISHED, SOURCE)
        assert row["not_given"] == ["block length", "noise schedule"]
    differs = {k for k, v in PUBLISHED.items() if CONFIG.get(k, "-") != v}
    assert differs == {"num_hidden_layers"} == set(CONFIG["reduced"])
    entry = mf.config_entry(MANIFEST, "sdar-30b-a3b-l7")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == CONFIG["source"] == SOURCE
    assert CONFIG["num_hidden_layers"] == 7
    assert CONFIG["torch_dtype"] == "bfloat16"
    assumed = CONFIG["assumed"]
    assert {k: assumed[k] for k in (
        "block_length", "denoising_steps", "remasking_strategy",
        "confidence_threshold", "temperature", "mask_token_id")} == {
        "block_length": 4, "denoising_steps": 4,
        "remasking_strategy": "low_confidence_dynamic",
        "confidence_threshold": 0.9, "temperature": 1.0,
        "mask_token_id": 151669}
    # every assumed size says where it comes from; so do the conventions
    assert set(assumed["sources"]) >= set(assumed) - {"sources"} | {
        "logits", "rope", "weights", "noise_schedule"}
    assert "tp = ep = dp = 1" in CONFIG["deployment"]
    correctness = CONFIG["correctness"]
    assert correctness["reference"] == "sdar_moe"
    # `correct` goes through the extend path: each chunk of one block is a
    # committing block pass behind a prefilled prefix
    assert correctness["extend_chunks"] >= 2
    assert correctness["extend_tokens"] == assumed["block_length"]
    assert correctness["decode_steps"] == 0
    assert (correctness["prefill_tokens"]
            + correctness["extend_chunks"] * correctness["extend_tokens"]
            ) % assumed["block_length"] == 0
    engine = CONFIG["engine"]
    assert (engine["num_slots"], engine["slot_capacity"],
            engine["kv_page_size"], engine["kv_pages"]) == (32, 2048, 128, 544)
    assert all(n % 4 == 0 for n in engine["prefill_buckets"])


def test_the_program_reads_the_configuration_as_a_block_mixture():
    from benchmark import launcher
    from llmlb_tpu.engine.scheduler import kv_page_bytes
    from llmlb_tpu.models import family_for, sdar_moe

    cfg = launcher.build_cfg(CONFIG)
    assert family_for(cfg) is sdar_moe
    assert (cfg.num_layers, cfg.num_experts, cfg.experts_per_token,
            cfg.moe_intermediate_size, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim_, cfg.vocab_size) == (7, 128, 8, 768, 32, 4, 128,
                                               151936)
    assert (cfg.block_length, cfg.denoising_steps, cfg.remasking_strategy,
            cfg.confidence_threshold, cfg.mask_token_id) == (
        4, 4, "low_confidence_dynamic", 0.9, 151669)
    # one page of the pool, and the weights, as the file's arithmetic has it
    assert kv_page_bytes(cfg, 128) == 7 * 128 * 2 * 4 * 128 * 2
    import jax

    shapes = jax.eval_shape(lambda k: sdar_moe.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    n_params = sum(v.size for v in shapes.values())
    assert abs(n_params * 2 / 1e9 - 9.97) < 0.01


def test_the_reference_is_found_by_the_configurations_name():
    from benchmark import reference

    module = reference.module_for(CONFIG)
    assert module.__name__.endswith("sdar_moe") and module.FOLLOWS == "routing"
    assert callable(module.generate)
    assert module.generation(CONFIG)["block_length"] == 4
    assert module.generation(CONFIG, denoising_steps=2)["denoising_steps"] == 2


def test_roofline_accounts_on_hand_worked_numbers():
    assert ROOFLINE.block_length(CONFIG) == 4
    # one call of the extend kernel: 1,000 live tokens, 10 rows of a block
    w = ROOFLINE.block_extend_call(CONFIG, live_tokens=1000, rows=10)
    assert w["bytes"] == 1000 * 4 * 128 * 2 * 2 + 10 * 4 * 32 * 128 * 2 * 2
    assert w["flops"] == 4 * 1000 * 4 * 32 * 128
    # grouped products: 100 experts touched by 300 assignments
    w = ROOFLINE.routed_experts(CONFIG, experts_touched=100, assignments=300)
    assert w["flops"] == 300 * 3 * 2 * 2048 * 768
    assert w["bytes"] == 100 * EXPERT_BYTES + 300 * (2 * 2048 + 3 * 768) * 2
    # a pass that touches 890 of the 7 x 128 = 896 experts it holds
    engine = {"param_bytes": 9_970_000_000, "n_params": 4_985_000_000}
    w = ROOFLINE.block_pass(CONFIG, engine, live_tokens=12_000, rows=32,
                            experts_touched=890)
    embed = 151936 * 2048
    assert w["bytes"] == (9_970_000_000 - embed * 2 - 6 * EXPERT_BYTES
                          + 12_000 * 7 * 4 * 128 * 2 * 2)
    active = 4_985_000_000 - embed - (896 - 7 * 8) * 3 * 2048 * 768
    assert w["flops"] == (2 * active * 32 * 4
                          + 7 * 4 * 12_000 * 4 * 32 * 128)
    # no kernel name here may be taken for a decode kernel's
    assert not any(n.startswith(("paged_flash_decode", "paged_latent_decode"))
                   for n in ROOFLINE.BLOCK_EXTEND_OPS + ROOFLINE.ROUTED_EXPERT_OPS)


def decode_record(ts, *, passes=10, rows=32, blocks=64, unmasked=256,
                  touched=8900, assignments=32 * 4 * 8 * 7 * 10):
    return {"kind": "decode", "ts": ts, "total_s": 0.2, "active_slots": rows,
            "tokens": blocks * 4, "block_passes": passes,
            "row_passes": passes * rows, "blocks_committed": blocks,
            "tokens_committed": blocks * 4, "positions_unmasked": unmasked,
            "experts_touched": touched, "expert_assignments": assignments,
            "expert_load_max": 20}


def collected(steps, trace=None):
    reqs = [{"first_s": 0.0, "last_s": 60.0, "prompt_tokens": 100,
             "words": 512} for _ in range(32)]
    return {"config": CONFIG, "steps": steps, "trace": trace, "peaks": V5E,
            "seconds": 51, "settings": SETTINGS, "requests": reqs,
            "engine": {"decode_burst": 10, "param_bytes": 9_970_000_000,
                       "n_params": 4_985_000_000}}


def read(name, c):
    return mf.load_module("layer_metrics", name).read(c)


def test_passes_per_token_on_hand_worked_numbers():
    # every block 4 unmasking passes and its commit: 320 row-passes for 64
    # blocks of 4 positions
    c = collected([decode_record(10.0), decode_record(10.2)])
    assert read("diffusion.passes_per_token", c) == 1.25
    # a burst in which half the rows had stopped, and a prefill between
    steps = [decode_record(10.0), {**decode_record(10.2), "row_passes": 160,
                                   "blocks_committed": 32,
                                   "tokens_committed": 128},
             {"kind": "prefill", "ts": 10.3, "total_s": 0.05, "tokens": 700,
              "active_slots": 8}]
    assert read("diffusion.passes_per_token", collected(steps)) == 480 / 384


def test_trace_readers_on_hand_worked_numbers():
    steps = [decode_record(100.1),  # in the traced part
             decode_record(90.0, touched=8000)]  # before it
    trace = {"wall_start": 99.0, "wall_stop": 107.0,
             "ops": {"grouped_expert_matmul_bf16_1024_768_": {"time_s": 0.5, "count": 140},
                     "grouped_expert_matmul_f32_1024_2048_": {"time_s": 0.3, "count": 70},
                     "paged_flash_extend_bf16_32_4_4_8_128_": {"time_s": 0.02, "count": 70},
                     "paged_flash_decode_bf16_32_8_4_128_": {"time_s": 9.0, "count": 1},
                     "fusion_bf16_128_2048_": {"time_s": 9.0, "count": 1}},
             "modules": {"jit_many(123)": {"count": 8, "time_s": 1.6,
                                           "median_s": 0.2}}}
    c = collected(steps, trace)
    w = ROOFLINE.routed_experts(CONFIG, experts_touched=8900,
                                assignments=32 * 4 * 8 * 7 * 10)
    want, bound = peaks.roofline_share_pct(w["flops"], w["bytes"], 0.8, V5E)
    assert read("kernel.block_routed_experts_roofline", c) == pytest.approx(want)
    assert 0 < want < 100 and bound == "memory"
    # every request holds 100 + 512 x t/60 tokens; 32 rows decode
    live = 32 * (100 + 512 * 47 / 60)
    w = ROOFLINE.block_extend_call(CONFIG, live_tokens=live, rows=32)
    want, _ = peaks.roofline_share_pct(w["flops"] * 70, w["bytes"] * 70, 0.02, V5E)
    assert read("kernel.block_extend_roofline", c) == pytest.approx(want, rel=1e-3)
    # both records are the window's: (8900 + 8000) / 20 experts a pass
    w = ROOFLINE.block_pass(CONFIG, c["engine"], live_tokens=live, rows=32,
                            experts_touched=(8900 + 8000) / 20)
    want, _ = peaks.roofline_share_pct(w["flops"], w["bytes"], 0.2 / 10, V5E)
    assert read("model.block_pass_roofline", c) == pytest.approx(want, rel=1e-3)
    assert 0 < want < 100


@pytest.mark.parametrize("name", READERS)
def test_readers_give_nothing_for_a_program_without_the_counters(name):
    """The parent commit, and every autoregressive family: step records
    without the block counts, a trace without the kernels. Nothing, and no
    exception."""
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
    """`run.py --rehearse --trace 1` on the block family through the real
    launcher, gateway and generator: `correct` with the routing heard, every
    request its exact count of words though a frame carries several, the
    block counts on the window's records, their reader in the line."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(mf.ROOT, "benchmark", "run.py"),
         "--manifest", REHEARSAL, "--workload", "tiny-sdar.closed", "--seed",
         "2147483655", "--seconds", "2", "--trace", "1", "--rehearse"],
        env=env, capture_output=True, text=True, timeout=280, cwd=mf.ROOT)
    assert proc.returncode == 4, proc.stderr[-3000:]
    split, line = map(json.loads, proc.stdout.strip().splitlines()[-2:])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert split["correctness"]["grounds"] == []
    assert split["correctness"]["decode_rel_rms_err"] is None  # no decode steps
    assert split["compiles_in_window"] == 0
    assert 0.5 <= line["metrics"]["diffusion.passes_per_token"]["value"] <= 1.25
    assert line["metrics"]["engine.programs_built_in_window"]["value"] == 0
    # device-trace readers find no device plane on the CPU: left out
    assert not set(line["metrics"]) & set(READERS[1:])


# --- benchmark/check_blocks.py: block passes at every position --------------

def _blocks(cases, capsys, monkeypatch, seed="5"):
    from benchmark import check_blocks

    rehearsal = os.path.dirname(REHEARSAL)
    monkeypatch.setattr(sys, "argv", [
        "check_blocks.py", "--config",
        os.path.join(rehearsal, "configs", "debug-sdar-tiny.json"), "--base",
        rehearsal, "--seeds", seed, "--cases", cases, "--rounds", "2"])
    monkeypatch.setattr(check_blocks, "ROOT", tempfile.mkdtemp())  # its log
    monkeypatch.setenv("LLMLB_INIT_TIMEOUT", "0")  # no watchdog under capsys
    assert check_blocks.main() == 0
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()
             if line.startswith("{")]
    return {line["case"]: line["result"] for line in lines}


def test_block_passes_agree_at_every_position_and_each_control_is_refused(
        capsys, monkeypatch):
    got = _blocks("program,causal_in_block,no_qk_norm,biased_choice,"
                  "int8_weights", capsys, monkeypatch)
    sound = got["program"]
    assert sound["ok"] is True and sound["max_rel_rms_err"] < 1e-4
    # four rows with 0 to 3 masks, a pass with masks and the commit, twice:
    # every count of masks at every position of the block
    assert set(sound["worst_by_masks_and_position"]) == {
        f"masked_{m}_at_{i}" for m in range(4) for i in range(4)}
    assert sound["passes"] == 2 * (4 + 3) and sound["dropped_assignments"] == 0
    for case in ("causal_in_block", "no_qk_norm"):
        assert got[case]["ok"] is False and "logits" in got[case]["grounds"]
        assert got[case]["max_rel_rms_err"] > 0.1
    # the causal mask is wrong where a position has masked or later ones to
    # see: everywhere but through the layers, the last position too
    assert got["causal_in_block"]["worst_by_masks_and_position"][
        "masked_0_at_0"] > 0.1
    # the wrong choice: sound logits (the reference follows it) and sound
    # scores, refused on two grounds, neither of them the logits
    wrong = got["biased_choice"]
    assert wrong["grounds"] == ["choice_is_own_topk", "flips_at_wide_margin"]
    assert wrong["max_rel_rms_err"] < 1e-4 and wrong["flips_at_wide_margin"] > 0
    control = got["int8_weights"]
    assert control["ok"] is False and "logits" in control["grounds"]
    assert 0.005 < control["max_rel_rms_err"] < 0.3


def test_the_precision_control_leaves_the_true_weights_behind(
        capsys, monkeypatch):
    """`program` after `int8_weights` in one seed reads rounding only: the
    weights were made again from the seed for the reference's pass."""
    got = _blocks("int8_weights,program", capsys, monkeypatch, seed="7")
    assert got["int8_weights"]["ok"] is False
    assert got["program"]["ok"] is True
    assert got["program"]["max_rel_rms_err"] < 1e-4
