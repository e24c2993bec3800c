"""The delta-rule hybrid's files in the benchmark (PR 48): its configuration
against the catalog row it was cut from, the operations and bytes of
benchmark/roofline/linear_hybrid.py and the four readers on hand-worked
numbers, what the readers give a program that has no such counters
(nothing), benchmark/check_linear.py and its controls at a CI size, and the
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
NAME = "olmo-hybrid-7b-l16"
CELL = NAME + ".decode-saturated"
CONFIG = mf.load_config(MANIFEST, NAME)
ROOFLINE = mf.load_module("roofline", "linear_hybrid")
HERE = os.path.dirname(os.path.abspath(__file__))
REHEARSAL = os.path.join(HERE, "rehearsal_linear", "BENCHMARK.json")
V5E = peaks.peaks_for("TPU v5 lite")
LAYER = {"model.linear_hybrid_decode_roofline": ("model step", "device_trace"),
         "kernel.delta_rule_step_roofline": ("kernels", "device_trace"),
         "kernel.linear_hybrid_attn_decode_roofline": ("kernels",
                                                       "device_trace"),
         "linear.state_bytes_share": ("model step", "program_counter")}
READERS = tuple(LAYER)
SOURCE = "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json"
PERIOD = ["linear_attention"] * 3 + ["full_attention"]
N_PARAMS = 4_100_788_944
STATE = 30 * 96 * 192  # one sequence's state in one layer, elements

with open(os.path.join(mf.HERE, "settings.json")) as f:
    SETTINGS = json.load(f)


def test_the_manifest_is_sound_and_the_cell_is_the_issues():
    assert mf.check(MANIFEST) == []
    cell = mf.cell(MANIFEST, CELL)
    assert cell == {**cell, "chips": 1, "config": NAME,
                    "traffic": "decode-saturated"}
    for said in ("closed loop", "32 callers", "64-128", "512 out",
                 "12 rule steps", "2.2 MB", "30x1", "16 dense"):
        assert said in cell["why"], said
    assert len(cell["why"]) <= 200
    assert [w["name"] for w in MANIFEST["workloads"]
            if w["config"] == NAME] == [CELL]  # no second cell
    traffic = mf.load_traffic("decode-saturated")  # as it was
    assert {k: traffic[k] for k in (
        "generator", "clients", "prompt", "max_tokens", "ramp_s",
        "start_after_tokens", "requests_per_client", "max_prefill_group")} == {
        "generator": "closed_loop", "clients": 32,
        "prompt": {"kind": "uniform", "lo": 64, "hi": 128},
        "max_tokens": 512, "ramp_s": 16, "start_after_tokens": 2,
        "requests_per_client": 8, "max_prefill_group": 8}
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name, (layer, source) in LAYER.items():
        assert by_name[name] == {
            "name": name, "unit": "%", "better": "higher", "source": source,
            "layer": layer, "moves": "tpot_p50_s", "workloads": [CELL]}
        assert os.path.exists(os.path.join(mf.HERE, "layer_metrics",
                                           name + ".py"))
    # the accepted readers that list their cells do not list this one
    for m in MANIFEST["per_layer"]:
        if m["name"] not in LAYER and "workloads" in m:
            assert CELL not in m["workloads"], m["name"]


def test_the_configuration_holds_the_published_keys_and_one_cut_of_depth():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Olmo-Hybrid-7B")
    published = row["config"]
    assert row["source_url"] == SOURCE == CONFIG["source"]
    differs = {k for k, v in published.items() if CONFIG.get(k, "-") != v}
    assert differs == {"num_hidden_layers", "layer_types"} == set(
        CONFIG["reduced"])
    assert published["layer_types"] == PERIOD * 8
    assert CONFIG["layer_types"] == PERIOD * 4 == published["layer_types"][:16]
    assert (published["num_hidden_layers"], CONFIG["num_hidden_layers"]) == (
        32, 16)
    assert CONFIG["reduced"]["num_hidden_layers"]["published"] == 32
    assert CONFIG["reduced"]["num_hidden_layers"]["here"] == 16
    entry = mf.config_entry(MANIFEST, NAME)
    assert sorted(entry["reduced"]) == ["layer_types", "num_hidden_layers"]
    assert entry["file"] == "benchmark/configs/olmo-hybrid-7b-l16.json"
    assert entry["source"] == SOURCE and len(entry["why"]) <= 200
    for key in entry["reduced"]:
        assert not mf.WIDTH_RE.search(key)  # no width is cut
    # every width as published
    assert (CONFIG["hidden_size"], CONFIG["intermediate_size"],
            CONFIG["vocab_size"], CONFIG["num_attention_heads"],
            CONFIG["num_key_value_heads"], CONFIG["linear_num_key_heads"],
            CONFIG["linear_num_value_heads"], CONFIG["linear_key_head_dim"],
            CONFIG["linear_value_head_dim"],
            CONFIG["linear_conv_kernel_dim"]) == (
        3840, 11008, 100352, 30, 30, 30, 30, 96, 192, 4)
    assert CONFIG["rope_parameters"] == {"rope_theta": None}
    assert CONFIG["linear_allow_neg_eigval"] is True
    assert CONFIG["torch_dtype"] == "bfloat16"
    assert set(CONFIG["assumed"]) >= {
        "block", "convolution", "qk_norm_linear", "decay_and_beta",
        "output_gate", "full_attention", "rotary_embedding", "state_dtype",
        "weights", "head_dim", "linear_projections"}
    assert "OUTPUT" in CONFIG["assumed"]["block"]
    assert "WITHOUT bias" in CONFIG["assumed"]["convolution"]
    assert "float32" in CONFIG["assumed"]["state_dtype"]
    assert "pipeline stage" in CONFIG["deployment"]
    correctness = CONFIG["correctness"]
    assert correctness["reference"] == "olmo_hybrid"
    assert (correctness["prefill_tokens"], correctness["extend_chunks"],
            correctness["extend_tokens"]) == (256, 2, 64)
    assert 8 <= correctness["decode_steps"] <= 16
    for text in (correctness["why"], *CONFIG["assumed"].values()):
        assert "TO BE SET" not in text and "provisional" not in text.lower()
    for said in ("14 seeds", "int8", "bf16", "not doubled", "decay"):
        assert said in correctness["why"], said
    engine = CONFIG["engine"]
    assert (engine["num_slots"], engine["slot_capacity"],
            engine["kv_page_size"], engine["kv_pages"], engine["decode_burst"],
            engine["prefix_cache"]) == (32, 2048, 128, 400, 8, False)
    assert engine["prefill_buckets"] == [32, 64, 128, 256, 512]


def test_the_program_reads_the_configuration_as_pages_and_a_state_a_slot():
    import jax

    from benchmark import launcher
    from llmlb_tpu.engine.scheduler import kv_page_bytes
    from llmlb_tpu.models import family_for, olmo_hybrid

    cfg = launcher.build_cfg(CONFIG)
    assert family_for(cfg) is olmo_hybrid
    assert (cfg.num_layers, cfg.layer_types, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim_, cfg.lin_heads, cfg.lin_key_dim, cfg.lin_value_dim,
            cfg.conv_kernel, cfg.allow_neg_eigval, cfg.rms_eps,
            cfg.vocab_size) == (16, tuple(PERIOD * 4), 30, 30, 128, 30, 96,
                                192, 4, True, 1e-6, 100352)
    record = olmo_hybrid.FAMILY
    assert record.kv_pool_layers(cfg) == 4
    # a cell as stored: 32 heads, two of them dead (8.39 MB a page); the
    # equations need 30 (7.86 MB), which is what the rooflines count
    assert record.kv_token_layer_bytes(cfg) == 2 * 32 * 128 * 2
    assert kv_page_bytes(cfg, 128) == 4 * 128 * 16384 == 8_388_608
    assert 4 * 128 * 30 * 128 * 2 * 2 == 7_864_320
    assert record.state_slot_bytes(cfg) == 12 * (STATE * 4 + 3 * 11520 * 2)
    assert 32 * record.state_slot_bytes(cfg) / 1e9 == pytest.approx(0.876,
                                                                    abs=1e-3)
    shapes = jax.eval_shape(lambda k: olmo_hybrid.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    assert sum(v.size for v in shapes.values()) == N_PARAMS
    assert N_PARAMS * 2 / 1e9 == pytest.approx(8.20, abs=5e-3)
    assert shapes["lin_wqkv"].shape == (12, 3840, 11520)
    assert shapes["wq"].shape == (4, 3840, 3840)
    assert shapes["wg"].shape == (16, 3840, 11008)
    pool = jax.eval_shape(lambda: olmo_hybrid.init_kv_pages(cfg, 400, 128,
                                                            num_slots=32))
    assert pool[0].pages.shape == (4, 400, 128, 32, 128)
    assert pool[0].state.shape == (12, 32, 96, 5760)
    assert pool[1].state.shape == (12, 3, 32, 11520)


def test_the_reference_is_found_by_the_configurations_name():
    from benchmark import reference

    module = reference.module_for(CONFIG)
    assert module.__name__.endswith("olmo_hybrid")
    assert not hasattr(module, "FOLLOWS")  # nothing is routed
    with open(module.__file__) as f:
        source = f.read()
    # independent of the program's ops: no kernel, cache, batching or chunk
    assert "llmlb_tpu" not in source
    assert 'default_matmul_precision("highest")' in source
    assert "pallas" not in source and "triangular" not in source
    assert "jax.lax.scan(token" in source  # the state, token by token


def test_roofline_accounts_on_the_issues_numbers():
    engine = {"param_bytes": 2 * N_PARAMS, "n_params": N_PARAMS}
    assert ROOFLINE.state_elements(CONFIG) == STATE
    assert STATE * 4 == 2_211_840  # 2.212 MB a layer and slot
    assert (ROOFLINE.layers(CONFIG, "linear_attention"),
            ROOFLINE.layers(CONFIG, "full_attention")) == (12, 4)
    one = ROOFLINE.step_call(CONFIG, rows=1)
    assert one["bytes"] == 2 * STATE * 4 + (2 * 2880 + 2 * 5760 + 60) * 4
    assert one["flops"] == 7 * STATE
    # 4.42 MB a (row, layer): 5.4 us at 819 GB/s, and memory-bound
    share, bound = peaks.roofline_share_pct(one["flops"], one["bytes"],
                                            5.5e-6, V5E)
    assert bound == "memory" and 98 < share < 100
    # a step's state: 12 layers x 32 rows x 4.42 MB = 1.70 GB (with the
    # convolution's rows, 1.75)
    w = ROOFLINE.decode_step(CONFIG, engine, live_tokens=32 * 350, rows=32)
    assert w["state_bytes"] == 32 * 12 * (2 * STATE * 4 + 2 * 3 * 11520 * 2)
    assert w["state_bytes"] / 1e9 == pytest.approx(1.75, abs=0.01)
    # live keys and values: 15,360 B a cell and layer, 0.69 GB at 350 tokens
    attn = ROOFLINE.attn_decode(CONFIG, cells=32 * 350 * 4, rows=32 * 4)
    assert attn["bytes"] == 32 * 350 * 4 * 15360 + 128 * 2 * 3840 * 2
    assert attn["bytes"] / 1e9 == pytest.approx(0.69, abs=0.005)
    assert attn["flops"] == 4 * 32 * 350 * 4 * 3840
    weights = 2 * (N_PARAMS - 100352 * 3840)
    assert weights / 1e9 == pytest.approx(7.43, abs=0.005)
    assert w["bytes"] == weights + w["state_bytes"] + attn["bytes"]
    assert w["bytes"] / 1e9 == pytest.approx(9.87, abs=0.02)
    share, bound = peaks.roofline_share_pct(w["flops"], w["bytes"], 0.015, V5E)
    assert bound == "memory" and 78 < share < 82  # 12.05 ms of 15
    # the state's part grows with the rows, not with the context
    assert 100 * w["state_bytes"] / w["bytes"] == pytest.approx(17.7, abs=0.2)
    long = ROOFLINE.decode_step(CONFIG, engine, live_tokens=32 * 2700, rows=32)
    assert long["state_bytes"] == w["state_bytes"]
    assert long["bytes"] - w["bytes"] == 32 * 2350 * 4 * 15360


def decode_record(ts, *, rows=32, burst=8, context=350):
    return {"kind": "decode", "ts": ts, "total_s": 0.12, "active_slots": rows,
            "tokens": rows * burst, "state_rows": rows * burst,
            "global_kv_tokens": rows * burst * 4 * context}


def collected(steps, trace=None, config=CONFIG):
    reqs = [{"first_s": 0.0, "last_s": 60.0, "prompt_tokens": 100,
             "words": 512} for _ in range(32)]
    return {"config": config, "steps": steps, "trace": trace, "peaks": V5E,
            "seconds": 51, "settings": SETTINGS, "requests": reqs,
            "engine": {"decode_burst": 8, "param_bytes": 2 * N_PARAMS,
                       "n_params": N_PARAMS}}


def read(name, c):
    return mf.load_module("layer_metrics", name).read(c)


def test_readers_on_hand_worked_numbers():
    steps = [decode_record(100.1),  # in the traced part
             decode_record(90.0, rows=16, context=600),  # before it
             {"kind": "prefill", "ts": 100.3, "total_s": 0.05, "tokens": 700,
              "active_slots": 8, "state_rows": 8, "global_kv_tokens": 2800,
              "scan_tokens": 700, "scan_chunks": 16}]
    trace = {"wall_start": 99.0, "wall_stop": 107.0,
             "ops": {"delta_rule_step_f32_32_1_5760_":
                     {"time_s": 0.0216, "count": 96},
                     "paged_flash_decode_bf16_32_32_128_":
                     {"time_s": 0.008, "count": 32},
                     "ssm_decode_step_f32_32_8_4096_":  # another kernel's
                     {"time_s": 7.0, "count": 1},
                     "fusion_bf16_32_11008_": {"time_s": 9.0, "count": 1}},
             "modules": {"jit_many(123)": {"count": 8, "time_s": 0.96,
                                           "median_s": 0.12}}}
    c = collected(steps, trace)
    w = ROOFLINE.step_call(CONFIG, rows=256 * 12)
    want, bound = peaks.roofline_share_pct(w["flops"], w["bytes"], 0.0216, V5E)
    assert read("kernel.delta_rule_step_roofline", c) == pytest.approx(want)
    assert 70 < want < 80 and bound == "memory"  # 5.4 of 7.03 us
    w = ROOFLINE.attn_decode(CONFIG, cells=256 * 4 * 350, rows=256 * 4)
    want, _ = peaks.roofline_share_pct(w["flops"], w["bytes"], 0.008, V5E)
    assert read("kernel.linear_hybrid_attn_decode_roofline", c
                ) == pytest.approx(want)
    assert 0 < want < 100
    w = ROOFLINE.decode_step(CONFIG, c["engine"], live_tokens=32 * 350,
                             rows=32)
    want, _ = peaks.roofline_share_pct(w["flops"], w["bytes"], 0.12 / 8, V5E)
    assert read("model.linear_hybrid_decode_roofline", c) == pytest.approx(
        want)
    assert 78 < want < 82
    # the state's share is over the whole window's decode records: 16 steps,
    # 24 rows a step and (32 x 350 + 16 x 600) / 2 tokens alive
    w = ROOFLINE.decode_step(CONFIG, c["engine"], live_tokens=10400, rows=24)
    assert read("linear.state_bytes_share", c) == pytest.approx(
        100 * w["state_bytes"] / w["bytes"])
    assert 13 < read("linear.state_bytes_share", c) < 15


@pytest.mark.parametrize("name", READERS)
def test_readers_give_nothing_for_a_program_without_the_counters(name):
    """The parent commit, and every other family: step records without the
    two counters, a trace without the kernel, another configuration.
    Nothing, and no exception."""
    plain = [{"kind": "decode", "ts": 100.0, "total_s": 0.2, "tokens": 256,
              "active_slots": 32}]
    hybrid = [{**plain[0], "state_rows": 256, "experts_touched": 900,
               "expert_assignments": 1500, "assignments_elsewhere": 1500,
               "expert_load_max": 9}]  # a state-space hybrid's record
    trace = {"wall_start": 99.0, "wall_stop": 107.0,
             "ops": {"paged_flash_decode_bf16_32_8_4_128_":
                     {"time_s": 1.0, "count": 10},
                     "ssm_decode_step_f32_32_8_4096_":
                     {"time_s": 1.0, "count": 10}},
             "modules": {"jit_many(1)": {"count": 8, "time_s": 1.6,
                                         "median_s": 0.2}}}
    assert read(name, collected(plain, trace)) is None
    assert read(name, collected(hybrid, trace)) is None
    assert read(name, collected([], None)) is None
    full = [decode_record(100.0)]  # this family's records, another's file
    ops = {**trace["ops"], "delta_rule_step_f32_32_1_5760_":
           {"time_s": 1.0, "count": 10}}
    for other in (c["name"] for c in MANIFEST["configs"] if c["name"] != NAME):
        c = collected(full, {**trace, "ops": ops},
                      mf.load_config(MANIFEST, other))
        assert read(name, c) is None, other


def test_the_cells_path_runs_end_to_end_on_the_cpu_at_a_ci_size():
    """`run.py --rehearse --trace 1` on the family through the real
    launcher, gateway and generator: a request through the gateway streams
    tokens, `correct` holds prefill, two extends and the decode steps to
    the reference, every request is served, the two counters are on the
    window's records and the counter reader in the line."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(mf.ROOT, "benchmark", "run.py"),
         "--manifest", REHEARSAL, "--workload", "tiny-linear.closed",
         "--seed", "2147483655", "--seconds", "3", "--trace", "1",
         "--rehearse"],
        env=env, capture_output=True, text=True, timeout=280, cwd=mf.ROOT)
    assert proc.returncode == 4, proc.stderr[-3000:]
    split, line = map(json.loads, proc.stdout.strip().splitlines()[-2:])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert split["correctness"]["ok"] is True
    assert split["correctness"]["positions_compared"] == 1 + 2 + 6
    assert split["correctness"]["max_rel_rms_err"] < 5e-5
    assert split["compiles_in_window"] == 0
    assert 2 <= line["metrics"]["linear.state_bytes_share"]["value"] <= 40
    assert line["metrics"]["engine.programs_built_in_window"]["value"] == 0
    # device-trace readers find no device plane on the CPU: left out
    assert not set(line["metrics"]) & set(READERS[:3])
    with open(os.path.join(mf.ROOT, ".bench_run", "tiny-linear.closed",
                           "last_run.json")) as f:
        steps = json.load(f)["steps"]
    decodes = [r for r in steps if r["kind"] == "decode"]
    assert decodes
    for r in decodes:  # every live row advanced, one attention layer
        assert r["state_rows"] == r["tokens"]
        assert r["global_kv_tokens"] >= r["tokens"] * 8
    assert any(r.get("scan_tokens") for r in steps if r["kind"] == "prefill")


# --- benchmark/check_linear.py: the controls of the new layers ---------------

def _linear(cases, capsys, monkeypatch, seed="5"):
    from benchmark import check_linear

    rehearsal = os.path.dirname(REHEARSAL)
    monkeypatch.setattr(sys, "argv", [
        "check_linear.py", "--config",
        os.path.join(rehearsal, "configs", "debug-olmo-hybrid-tiny.json"),
        "--base", rehearsal, "--seeds", seed, "--cases", cases])
    monkeypatch.setattr(check_linear, "ROOT", tempfile.mkdtemp())  # its log
    monkeypatch.setenv("LLMLB_INIT_TIMEOUT", "0")  # no watchdog under capsys
    assert check_linear.main() == 0
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()
             if line.startswith("{")]
    return {line["case"]: line for line in lines}


def test_the_sound_program_passes_and_every_control_is_refused(capsys,
                                                               monkeypatch):
    from benchmark import check_linear

    got = _linear(check_linear.CASES, capsys, monkeypatch)
    assert set(got) == set(check_linear.CASES.split(","))
    for case in ("program", "interleaved_decode"):
        sound = got[case]["result"]
        assert sound["ok"] is True and sound["max_rel_rms_err"] < 5e-5, case
    # `live` false left the state where it was, to the last digit
    assert (got["interleaved_decode"]["result"]["max_rel_rms_err"]
            == got["program"]["result"]["max_rel_rms_err"])
    for case in ("live_mask_off", "int8_weights", "state_bf16",
                 "beta_not_doubled", "no_decay", "conv_not_carried"):
        result = got[case]["result"]
        assert result["ok"] is False and result["max_rel_rms_err"] > 1e-3, case
    for case in ("live_mask_off", "beta_not_doubled", "no_decay",
                 "conv_not_carried"):
        assert got[case]["result"]["max_rel_rms_err"] > 0.1, case


def test_the_precision_control_leaves_the_true_weights_behind(capsys,
                                                              monkeypatch):
    got = _linear("int8_weights,program", capsys, monkeypatch, seed="7")
    assert got["int8_weights"]["result"]["ok"] is False
    assert got["program"]["result"]["ok"] is True
    assert (got["program"]["result"]["max_rel_rms_err"] < 5e-5
            < got["int8_weights"]["result"]["max_rel_rms_err"])
