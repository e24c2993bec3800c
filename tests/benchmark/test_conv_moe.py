"""The gated-short-convolution mixture's files in the benchmark (PR 59): its
configuration against the catalog row it holds key for key but its two
cuts, the operations and bytes of benchmark/roofline/conv_moe.py on the
issue's arithmetic, the five readers on hand-worked numbers — and on a trace
that holds other steps than the records, which must not move them —, what
the readers give a program that has no such counters (nothing),
benchmark/check_conv_moe.py and its controls at a CI size, and the new
cell's path end to end on the CPU (`run.py --rehearse`).

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
NAME = "lfm2-24b-a2b-l10"
CELL = NAME + ".decode-saturated"
CONFIG = mf.load_config(MANIFEST, NAME)
ROOFLINE = mf.load_module("roofline", "conv_moe")
HERE = os.path.dirname(os.path.abspath(__file__))
REHEARSAL = os.path.join(HERE, "rehearsal_conv_moe", "BENCHMARK.json")
V5E = peaks.peaks_for("TPU v5 lite")
LAYER = {"model.conv_moe_decode_roofline": ("model step", "device_trace"),
         "kernel.conv_moe_experts_roofline": ("kernels", "device_trace"),
         "kernel.conv_moe_attn_decode_roofline": ("kernels", "device_trace"),
         "moe.conv_experts_touched_share": ("model step", "program_counter"),
         "conv.mixer_bytes_share": ("model step", "program_counter")}
READERS = tuple(LAYER)
SOURCE = ("https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/"
          "config.json")
N_PARAMS = 5_267_090_176  # the issue's 5,267 M
MATRIX = 2048 * 1536  # one of an expert's three
ENGINE = {"decode_burst": 8, "param_bytes": 2 * N_PARAMS + 2 * 8 * 64,
          "n_params": N_PARAMS}  # the choice bias is float32

with open(os.path.join(mf.HERE, "settings.json")) as f:
    SETTINGS = json.load(f)


def test_the_manifest_is_sound_and_the_cell_is_the_issues():
    assert mf.check(MANIFEST) == []
    cell = mf.cell(MANIFEST, CELL)
    assert cell == {**cell, "chips": 1, "config": NAME,
                    "traffic": "decode-saturated"}
    for said in ("closed loop", "32 callers", "64-128", "512 out",
                 "8 conv mixers", "2 attentions", "8 mixtures",
                 "56 of 64"):
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
    # and it reports every metric that lists no cells and moves what it does
    reported = {m["name"] for m in mf.metrics_for(MANIFEST, "per_layer", CELL)}
    assert reported >= set(LAYER) | {"model.decode_step_s",
                                     "device.hbm_peak_bytes"}
    assert {m["name"] for m in mf.metrics_for(MANIFEST, "end_to_end", CELL)
            } == {"tpot_p50_s", "setup_s"}


def test_the_configuration_holds_the_published_keys_and_its_two_cuts():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "LFM2-24B-A2B")
    published = row["config"]
    assert row["source_url"] == SOURCE == CONFIG["source"]
    cut = {"num_hidden_layers", "layer_types"}
    assert {k for k, v in published.items() if CONFIG.get(k, "-") != v
            } == cut == set(CONFIG["reduced"])
    entry = mf.config_entry(MANIFEST, NAME)
    assert set(entry["reduced"]) == cut
    assert entry["file"] == "benchmark/configs/lfm2-24b-a2b-l10.json"
    assert entry["source"] == SOURCE and len(entry["why"]) <= 200
    assert CONFIG["num_hidden_layers"] == 10
    assert CONFIG["layer_types"] == published["layer_types"][:10]
    assert [i for i, kind in enumerate(CONFIG["layer_types"])
            if kind == "full_attention"] == [2, 6]
    assert CONFIG["reduced"]["num_hidden_layers"]["published"] == 40
    # no width, head count, expert, experts a token or vocabulary row differs
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "num_key_value_heads", "num_experts",
                "num_experts_per_tok", "vocab_size", "conv_L_cache",
                "num_dense_layers"):
        assert CONFIG[key] == published[key], key
    assert CONFIG["torch_dtype"] == "bfloat16"
    assert set(CONFIG["assumed"]) >= {
        "head_dim", "tied_head", "qk_norm", "rotary", "gated_conv", "route",
        "expert_bias", "carried_rows", "weights", "page_pool"}
    assert "64" in CONFIG["assumed"]["head_dim"]
    assert "1e-6" in CONFIG["assumed"]["route"]
    assert "NO activation" in CONFIG["assumed"]["gated_conv"]
    assert "bf16" in CONFIG["assumed"]["carried_rows"]
    assert "four stages" in CONFIG["deployment"]
    assert "stage 0" in CONFIG["deployment"]
    correctness = CONFIG["correctness"]
    assert correctness["reference"] == "lfm2_moe"
    assert (correctness["prefill_tokens"], correctness["extend_chunks"],
            correctness["extend_tokens"], correctness["decode_steps"]) == (
        256, 2, 64, 16)
    assert {"tolerance", "router_tolerance", "flip_margin_multiple"} <= set(
        correctness)
    for text in (correctness["why"], CONFIG["deployment"],
                 CONFIG["engine"]["kv_pool_arithmetic"],
                 *CONFIG["assumed"].values()):
        assert "TODO" not in text and "provisional" not in text.lower()
        assert "TO BE SET" not in text
    for said in ("seeds", "int8", "gate", "silu", "carried", "live",
                 "norm", "rotary", "bias", "zeroed"):
        assert said in correctness["why"], said
    engine = CONFIG["engine"]
    assert (engine["num_slots"], engine["slot_capacity"],
            engine["kv_page_size"], engine["kv_pages"], engine["decode_burst"],
            engine["prefix_cache"]) == (32, 2048, 128, 544, 8, False)
    assert engine["prefill_buckets"] == [32, 64, 128, 256, 512]
    assert "5,267,090,176" in engine["kv_pool_arithmetic"]


def test_the_program_reads_the_configuration_as_pages_and_two_rows_a_slot():
    import jax

    from benchmark import launcher
    from llmlb_tpu.engine.scheduler import kv_page_bytes
    from llmlb_tpu.models import family_for, lfm2_moe

    cfg = launcher.build_cfg(CONFIG)
    assert family_for(cfg) is lfm2_moe
    assert (cfg.num_layers, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_,
            cfg.conv_taps, cfg.num_dense_layers, cfg.num_experts,
            cfg.experts_per_token, cfg.moe_intermediate_size,
            cfg.vocab_size, cfg.rope_theta) == (
        10, 32, 8, 64, 3, 2, 64, 4, 1536, 65536, 1e6)
    record = lfm2_moe.FAMILY
    assert record.kv_pool_layers(cfg) == 2
    assert record.kv_token_layer_bytes(cfg) == 2 * 8 * 64 * 2
    assert kv_page_bytes(cfg, 128) == 2 * 128 * 2048 == 524_288
    assert 544 * 524_288 / 1e9 == pytest.approx(0.285, abs=1e-3)
    assert record.state_slot_bytes(cfg) == 8 * 2 * 2048 * 2  # 64 KB a slot
    shapes = jax.eval_shape(lambda k: lfm2_moe.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    assert sum(v.size for v in shapes.values()) == N_PARAMS
    assert sum(v.size * v.dtype.itemsize for v in shapes.values()
               ) == ENGINE["param_bytes"]
    assert ENGINE["param_bytes"] / 1e9 == pytest.approx(10.53, abs=5e-3)
    pool = jax.eval_shape(lambda: lfm2_moe.init_kv_pages(
        cfg, 544, 128, num_slots=32))
    assert pool[0].pages.shape == (2, 544, 128, 4, 128)  # two heads a row
    assert pool[0].state.shape == (8, 32, 2, 2048)
    assert pool[1].state.size == 0


def test_the_parent_class_refuses_the_configuration_at_once():
    """What the tree before PR 59 does with the new cell: no family names
    `lfm2_moe`, the file carries `num_experts`, so it is read for Mixtral's
    class, which is refused a key it does not compute by name before
    anything is built."""
    from llmlb_tpu.models import config_from_hf

    with pytest.raises(ValueError, match="does not compute"):
        config_from_hf({**{k: v for k, v in CONFIG.items()
                           if not isinstance(v, dict)},
                        "model_type": "a_type_nobody_registered"})


def test_the_reference_is_found_by_the_configurations_name():
    from benchmark import reference

    module = reference.module_for(CONFIG)
    assert module.__name__.endswith("lfm2_moe")
    assert module.FOLLOWS == "routing"
    with open(module.__file__) as f:
        source = f.read()
    # independent of the program's ops: no kernel, cache, batching or pool
    assert "llmlb_tpu" not in source
    assert 'default_matmul_precision("highest")' in source
    assert "pallas" not in source and "causal_conv" not in source
    assert "1e-6" in source and "silu" not in source.split("def conv_layer")[
        1].split("def attention_layer")[0]


def test_roofline_accounts_on_the_issues_numbers():
    assert (ROOFLINE.layers(CONFIG, "conv"),
            ROOFLINE.layers(CONFIG, "full_attention"),
            ROOFLINE.moe_layers(CONFIG)) == (8, 2, 8)
    assert ROOFLINE.matrix_params(CONFIG) == MATRIX
    assert ROOFLINE.expert_slots(CONFIG) == 512
    # a conv mixer is 16.78 M parameters; its rows 8 KB a slot and layer
    assert ROOFLINE.conv_mixer_params(CONFIG) == (
        2048 * 6144 + 2048 * 2048 + 3 * 2048 + 2048)
    assert ROOFLINE.conv_mixer_params(CONFIG) / 1e6 == pytest.approx(
        16.78, abs=0.01)
    assert ROOFLINE.carried_bytes(CONFIG) == 2 * 8192
    # one of a layer's three products at 56 of 64 touched, 128 assignments
    one = ROOFLINE.experts_call(CONFIG, experts_touched=56, assignments=128)
    assert one["bytes"] == 56 * MATRIX * 2 + 128 * (2048 + 1536) * 2
    assert one["flops"] == 2 * 128 * MATRIX
    share, bound = peaks.roofline_share_pct(one["flops"], one["bytes"],
                                            4.4e-4, V5E)
    assert bound == "memory" and 97 < share < 100  # 0.43 ms at 819 GB/s
    attn = ROOFLINE.attn_decode_call(CONFIG, cells=32 * 350 * 2, rows=32 * 2)
    assert attn["bytes"] == 32 * 350 * 2 * 2048 + 64 * 2 * 2048 * 2
    assert attn["flops"] == 4 * 32 * 350 * 2 * 2048
    # a step at 32 rows, contexts of 350 and 56 of 64 touched a layer: the
    # issue's 9.3 GB, the experts 8.44 of it, the conv mixers 0.27
    w = ROOFLINE.decode_step(CONFIG, ENGINE, rows=32, conv_rows=32 * 8,
                             live_cells=32 * 350 * 2, experts_touched=56 * 8)
    experts = 56 * 8 * 3 * MATRIX * 2
    assert experts / 1e9 == pytest.approx(8.46, abs=0.03)
    assert w["bytes"] == (ENGINE["param_bytes"] - 64 * 3 * MATRIX * 2
                          + 32 * 8 * 2 * 8192 + attn["bytes"])
    assert w["bytes"] / 1e9 == pytest.approx(9.37, abs=0.05)
    assert w["conv_bytes"] == 8 * ROOFLINE.conv_mixer_params(CONFIG) * 2 + (
        32 * 8 * 2 * 8192)
    assert w["conv_bytes"] / 1e9 == pytest.approx(0.273, abs=0.003)
    assert 100 * w["conv_bytes"] / w["bytes"] == pytest.approx(2.9, abs=0.15)
    share, bound = peaks.roofline_share_pct(w["flops"], w["bytes"], 0.015, V5E)
    assert bound == "memory" and 74 < share < 78  # 11.4 ms of 15
    # every expert touched is every weight read: the chip's 10.53 GB
    full = ROOFLINE.decode_step(CONFIG, ENGINE, rows=32, conv_rows=0,
                                live_cells=0, experts_touched=512)
    assert full["bytes"] == ENGINE["param_bytes"] + 64 * 2 * 2048 * 2
    # the mixers' part does not grow with the context
    long = ROOFLINE.decode_step(CONFIG, ENGINE, rows=32, conv_rows=32 * 8,
                                live_cells=32 * 2000 * 2,
                                experts_touched=56 * 8)
    assert long["conv_bytes"] == w["conv_bytes"]
    assert long["bytes"] - w["bytes"] == 32 * 1650 * 2 * 2048


def decode_record(ts, *, rows=32, burst=8, context=350, touched=56):
    return {"kind": "decode", "ts": ts, "total_s": 0.12, "active_slots": rows,
            "tokens": rows * burst, "conv_rows": rows * burst * 8,
            "global_kv_tokens": rows * burst * 2 * context,
            "experts_touched": burst * 8 * touched,
            "expert_assignments": rows * burst * 8 * 4,
            "expert_load_max": 7}


def collected(steps, trace=None, config=CONFIG):
    return {"config": config, "steps": steps, "trace": trace, "peaks": V5E,
            "seconds": 51, "settings": SETTINGS, "requests": [],
            "engine": ENGINE}


def read(name, c):
    return mf.load_module("layer_metrics", name).read(c)


def traced(expert_calls=192, attn_calls=16):
    """8 steps of a burst: 8 mixtures of three products and 2 attentions
    each."""
    return {"wall_start": 99.0, "wall_stop": 107.0, "device_planes": 1,
            "ops": {"grouped_expert_matmul_bf16_128_1536_":
                    {"time_s": expert_calls * 2 / 3 * 5.2e-4,
                     "count": expert_calls * 2 // 3},
                    "grouped_expert_matmul_f32_128_2048_":
                    {"time_s": expert_calls / 3 * 5.2e-4,
                     "count": expert_calls // 3},
                    "paged_flash_decode_bf16_32_32_128_":
                    {"time_s": attn_calls * 4e-5, "count": attn_calls},
                    "ssm_decode_step_f32_32_8_4096_":  # another kernel's
                    {"time_s": 7.0, "count": 1},
                    "fusion_bf16_32_8192_": {"time_s": 9.0, "count": 1}},
            "modules": {"jit_many(123)": {"count": 8, "time_s": 0.96,
                                          "median_s": 0.12}}}


def test_readers_on_hand_worked_numbers():
    steps = [decode_record(100.1),  # in the traced part
             decode_record(90.0, rows=16, context=600, touched=40)]  # before
    c = collected(steps, traced())
    # 192 calls, each a matrix of 56 experts and 128 assignments
    w = ROOFLINE.experts_call(CONFIG, experts_touched=192 * 56,
                              assignments=192 * 128)
    want, bound = peaks.roofline_share_pct(w["flops"], w["bytes"],
                                           192 * 5.2e-4, V5E)
    assert read("kernel.conv_moe_experts_roofline", c) == pytest.approx(want)
    assert 80 < want < 86 and bound == "memory"
    w = ROOFLINE.attn_decode_call(CONFIG, cells=16 * 32 * 350, rows=16 * 32)
    want, _ = peaks.roofline_share_pct(w["flops"], w["bytes"], 16 * 4e-5, V5E)
    assert read("kernel.conv_moe_attn_decode_roofline", c
                ) == pytest.approx(want)
    assert 0 < want < 100
    w = ROOFLINE.decode_step(CONFIG, ENGINE, rows=32, conv_rows=256,
                             live_cells=32 * 350 * 2, experts_touched=448)
    want, _ = peaks.roofline_share_pct(w["flops"], w["bytes"], 0.12 / 8, V5E)
    assert read("model.conv_moe_decode_roofline", c) == pytest.approx(want)
    assert 74 < want < 78
    # the two counter readers are over the whole window's decode records: 16
    # steps, 24 rows and (56 + 40) / 2 of 64 touched a layer and step
    assert read("moe.conv_experts_touched_share", c) == pytest.approx(
        100 * 48 / 64)
    w = ROOFLINE.decode_step(CONFIG, ENGINE, rows=24, conv_rows=24 * 8,
                             live_cells=(32 * 350 + 16 * 600),
                             experts_touched=48 * 8)
    assert read("conv.mixer_bytes_share", c) == pytest.approx(
        100 * w["conv_bytes"] / w["bytes"])
    assert 3 < read("conv.mixer_bytes_share", c) < 4


def test_a_prefill_in_the_traced_part_counts_its_own_experts():
    """A prefill's three products a layer run under the decode step's names
    and touch every expert: the experts' reader takes what a call moved on
    average over the traced records of ANY kind (9 model steps here), the
    two readers of a decode step over the decode records alone."""
    prefill = {"kind": "prefill", "ts": 100.3, "total_s": 0.05,
               "tokens": 700, "active_slots": 8, "conv_rows": 64,
               "global_kv_tokens": 1400, "experts_touched": 512,
               "expert_assignments": 700 * 8 * 4, "expert_load_max": 90}
    c = collected([decode_record(100.1), prefill], traced(expert_calls=216))
    w = ROOFLINE.experts_call(
        CONFIG, experts_touched=216 * (8 * 448 + 512) / (9 * 8),
        assignments=216 * (8 * 1024 + 22400) / (9 * 8))
    want, _ = peaks.roofline_share_pct(w["flops"], w["bytes"],
                                       216 * 5.2e-4, V5E)
    assert read("kernel.conv_moe_experts_roofline", c) == pytest.approx(want)
    alone = collected([decode_record(100.1)], traced(expert_calls=216))
    assert read("model.conv_moe_decode_roofline", c) == pytest.approx(
        read("model.conv_moe_decode_roofline", alone))
    assert read("moe.conv_experts_touched_share", c) == pytest.approx(87.5)


@pytest.mark.parametrize("held", [0.5, 1.0, 1.5])
def test_a_trace_that_holds_other_steps_than_the_records_moves_no_share(held):
    """The calls come from the trace's own rows
    (kernel.ssm_dense_step_roofline says why): a trace that holds half the
    records' steps, or half as many again, reads the same shares."""
    steps = [decode_record(100.1)]
    whole = collected(steps, traced())
    other = collected(steps, traced(expert_calls=int(192 * held),
                                    attn_calls=int(16 * held)))
    for name in READERS[:3]:
        assert read(name, other) == pytest.approx(read(name, whole),
                                                  rel=1e-9), name
        assert read(name, other) < 100


@pytest.mark.parametrize("name", READERS)
def test_readers_give_nothing_for_a_program_without_the_counters(name):
    """The parent commit, and every other family: step records without the
    counters, a trace without the kernels, another configuration. Nothing,
    and no exception."""
    plain = [{"kind": "decode", "ts": 100.0, "total_s": 0.2, "tokens": 256,
              "active_slots": 32}]
    routed = [{**plain[0], "experts_touched": 900, "expert_assignments": 1500,
               "expert_load_max": 9, "global_kv_tokens": 9000,
               "window_kv_tokens": 9000}]  # a window mixture's record
    trace = traced()
    assert read(name, collected(plain, trace)) is None
    assert read(name, collected(routed, trace)) is None
    assert read(name, collected([], None)) is None
    assert read(name, collected([], trace)) is None
    full = [decode_record(100.0)]  # this family's records, another's file
    for other in (c["name"] for c in MANIFEST["configs"] if c["name"] != NAME):
        c = collected(full, trace, mf.load_config(MANIFEST, other))
        assert read(name, c) is None, other
    if LAYER[name][1] == "device_trace":  # the records, and no kernel rows
        bare = {**trace, "ops": {}, "modules": {}}
        assert read(name, collected(full, bare)) is None


def test_the_cells_path_runs_end_to_end_on_the_cpu_at_a_ci_size():
    """`run.py --rehearse --trace 1` on the family through the real
    launcher, gateway and generator: `correct` holds prefill, two extends
    and the decode steps to the reference with the routing followed, every
    request is served, the counters are on the window's records and the
    counter readers in the line."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(mf.ROOT, "benchmark", "run.py"),
         "--manifest", REHEARSAL, "--workload", "tiny-conv-moe.closed",
         "--seed", "2147483655", "--seconds", "3", "--trace", "1",
         "--rehearse"],
        env=env, capture_output=True, text=True, timeout=280, cwd=mf.ROOT)
    assert proc.returncode == 4, proc.stderr[-3000:]
    split, line = map(json.loads, proc.stdout.strip().splitlines()[-2:])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert split["correctness"]["ok"] is True
    assert split["correctness"]["positions_compared"] == 1 + 2 + 6
    assert split["correctness"]["max_rel_rms_err"] < 1e-5
    assert split["correctness"]["router_rel_rms_err"] < 1e-5
    assert split["correctness"]["dropped_assignments"] == 0
    assert split["compiles_in_window"] == 0
    metrics = line["metrics"]
    assert 20 <= metrics["moe.conv_experts_touched_share"]["value"] <= 100
    assert 5 <= metrics["conv.mixer_bytes_share"]["value"] <= 60
    assert metrics["engine.programs_built_in_window"]["value"] == 0
    # device-trace readers find no device plane on the CPU: left out
    assert not set(metrics) & set(READERS[:3])
    with open(os.path.join(mf.ROOT, ".bench_run", "tiny-conv-moe.closed",
                           "last_run.json")) as f:
        steps = json.load(f)["steps"]
    decodes = [r for r in steps if r["kind"] == "decode"]
    assert decodes
    for r in decodes:  # every live row moved in six conv layers
        assert r["conv_rows"] == 6 * r["tokens"]
        assert r["global_kv_tokens"] >= r["tokens"] * 2 * 8
        assert r["expert_assignments"] == 6 * 2 * r["tokens"]
        assert 0 < r["experts_touched"] <= 6 * 8 * (
            r["tokens"] // r["active_slots"])
    assert any(r["conv_rows"] and r["expert_load_max"] > 1
               for r in steps if r["kind"] == "prefill")


# --- benchmark/check_conv_moe.py: the controls of what is new ----------------

def _checked(cases, capsys, monkeypatch, seed="5"):
    from benchmark import check_conv_moe

    rehearsal = os.path.dirname(REHEARSAL)
    monkeypatch.setattr(sys, "argv", [
        "check_conv_moe.py", "--config",
        os.path.join(rehearsal, "configs", "debug-lfm2-moe-tiny.json"),
        "--base", rehearsal, "--seeds", seed, "--cases", cases])
    monkeypatch.setattr(check_conv_moe, "ROOT", tempfile.mkdtemp())  # its log
    monkeypatch.setenv("LLMLB_INIT_TIMEOUT", "0")  # no watchdog under capsys
    assert check_conv_moe.main() == 0
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()
             if line.startswith("{")]
    return {line["case"]: line for line in lines}


def test_the_sound_program_passes_and_every_control_is_refused(capsys,
                                                               monkeypatch):
    from benchmark import check_conv_moe

    got = _checked(check_conv_moe.CASES, capsys, monkeypatch)
    assert set(got) == set(check_conv_moe.CASES.split(","))
    for case in ("program", "interleaved_decode"):
        sound = got[case]["result"]
        assert sound["ok"] is True and sound["max_rel_rms_err"] < 1e-5, case
    # `live` false left the carried rows where they were, to the last digit
    assert (got["interleaved_decode"]["result"]["max_rel_rms_err"]
            == got["program"]["result"]["max_rel_rms_err"])
    for case in ("live_mask_off", "int8_weights", "no_b_gate",
                 "silu_behind_conv", "conv_not_carried", "no_qk_norm",
                 "no_rotary", "zeroed_chosen_expert"):
        result = got[case]["result"]
        assert result["ok"] is False and "logits" in result["grounds"], case
        assert result["max_rel_rms_err"] > 1e-3, case
    # in float32 the program's choices ARE the reference's: nothing to follow
    assert got["unfollowed"]["result"]["ok"] is True
    assert got["unfollowed"]["result"]["flips"] == 0
    # chosen without the bias: sound logits and scores, the choice wrong
    unbiased = got["unbiased_choice"]["result"]
    assert unbiased["ok"] is False
    assert "flips_at_wide_margin" in unbiased["grounds"]
    assert "logits" not in unbiased["grounds"]
    assert got["zeroed_chosen_expert"]["read_by"] >= 1
    for case in ("no_b_gate", "silu_behind_conv", "no_rotary"):
        assert got[case]["result"]["max_rel_rms_err"] > 0.05, case


def test_the_precision_control_leaves_the_true_weights_behind(capsys,
                                                              monkeypatch):
    got = _checked("int8_weights,program", capsys, monkeypatch, seed="7")
    assert got["int8_weights"]["result"]["ok"] is False
    assert got["program"]["result"]["ok"] is True
    assert (got["program"]["result"]["max_rel_rms_err"] < 1e-5
            < got["int8_weights"]["result"]["max_rel_rms_err"])
