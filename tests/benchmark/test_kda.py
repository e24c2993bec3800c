"""Kimi-Linear's files in the benchmark (PR 62): its configuration against the
catalog row it holds key for key but its one cut, the operations and bytes
of benchmark/roofline/kda.py on the issue's arithmetic, the six readers on
hand-worked numbers — and on a trace that holds other steps than the
records, which must not move them —, what the readers give a program that
has no such counters (nothing), benchmark/check_kda.py and its controls at a
CI size, and the new cell's path end to end on the CPU (`run.py --rehearse`).

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
NAME = "kimi-linear-48b-a3b"
CELL = NAME + ".decode-saturated"
CONFIG = mf.load_config(MANIFEST, NAME)
ROOFLINE = mf.load_module("roofline", "kda")
HERE = os.path.dirname(os.path.abspath(__file__))
REHEARSAL = os.path.join(HERE, "rehearsal_kda", "BENCHMARK.json")
V5E = peaks.peaks_for("TPU v5 lite")
LAYER = {"model.kda_moe_decode_roofline": ("model step", "device_trace"),
         "kernel.kda_step_roofline": ("kernels", "device_trace"),
         "kernel.kda_latent_decode_roofline": ("kernels", "device_trace"),
         "kernel.kda_held_experts_roofline": ("kernels", "device_trace"),
         "linear.kda_state_bytes_share": ("model step", "program_counter"),
         "moe.kda_held_assignment_share": ("model step", "program_counter")}
READERS = tuple(LAYER)
SOURCE = ("https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/"
          "blob/main/config.json")
N_PARAMS = 4_956_660_608  # the issue's 4,957 M
EXPERT = 3 * 2304 * 1024  # one expert's three matrices
STATE = 32 * 128 * 128  # one row's state in one layer, in numbers
# the choice bias, A_log and dt_bias are float32
ENGINE = {"decode_burst": 8, "n_params": N_PARAMS,
          "param_bytes": 2 * N_PARAMS + 2 * (26 * 256 + 20 * (4096 + 32))}

with open(os.path.join(mf.HERE, "settings.json")) as f:
    SETTINGS = json.load(f)


def test_the_manifest_is_sound_and_the_cell_is_the_issues():
    assert mf.check(MANIFEST) == []
    cell = mf.cell(MANIFEST, CELL)
    assert cell == {**cell, "chips": 1, "config": NAME,
                    "traffic": "decode-saturated"}
    for said in ("closed loop", "32 callers", "64-128", "512 out",
                 "20 KDA steps", "7 latent attentions", "26 mixtures"):
        assert said in cell["why"], said
    assert len(cell["why"]) <= 200
    assert [w["name"] for w in MANIFEST["workloads"]
            if w["config"] == NAME] == [CELL]  # no second cell
    entry = next(c for c in MANIFEST["configs"] if c["name"] == NAME)
    assert entry == {**entry, "source": SOURCE, "reduced": ["num_experts"],
                     "file": f"benchmark/configs/{NAME}.json"}
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


def test_the_configuration_holds_the_published_keys_and_its_one_cut():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    assert row["source_url"] == SOURCE == CONFIG["source"]
    differs = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
    assert differs == {"num_experts"} == set(CONFIG["reduced"])
    cut = CONFIG["reduced"]["num_experts"]
    assert (cut["published"], cut["here"]) == (256, 16) == (
        row["config"]["num_experts"], CONFIG["num_experts"])
    assert CONFIG["expert_parallel"] == {"chips": 16, "chip": 0,
                                         "experts": 256}
    assert CONFIG["num_hidden_layers"] == 27 == row["layers"]
    assert CONFIG["vocab_size"] == 163840
    for said in ("biases", "decay_and_beta", "qk_norm", "state_dtype",
                 "e_score_correction_bias", "weights", "latent_attention"):
        assert said in CONFIG["assumed"], said
    assert "16 v5e chips" in CONFIG["deployment"]
    assert CONFIG["engine"] == {**CONFIG["engine"], "num_slots": 32,
                                "slot_capacity": 2048, "kv_page_size": 128,
                                "kv_pages": 544, "decode_burst": 8,
                                "prefix_cache": False}
    spec = CONFIG["correctness"]
    assert (spec["reference"], spec["prefill_tokens"], spec["extend_chunks"],
            spec["extend_tokens"], spec["decode_steps"]) == (
        "kimi_linear", 256, 2, 64, 16)
    for key in ("tolerance", "router_tolerance", "flip_margin_multiple"):
        assert spec[key] > 0 and key in spec["why"], key


def test_the_program_reads_the_configuration_as_a_state_beside_latent_pages():
    import jax

    from benchmark import launcher
    from llmlb_tpu.models import family_for, kimi_linear

    cfg = launcher.build_cfg(CONFIG)
    family = family_for(cfg)
    assert family is kimi_linear
    assert cfg.held_experts == (0, 16) and cfg.router_experts == 256
    shapes = jax.eval_shape(lambda k: family.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    n = sum(v.size for v in shapes.values())
    nbytes = sum(v.size * v.dtype.itemsize for v in shapes.values())
    assert (n, nbytes) == (N_PARAMS, ENGINE["param_bytes"])
    assert 9.81e9 < nbytes < 10.01e9  # the issue's 9.91 GB +- 0.1
    engine = CONFIG["engine"]
    state = engine["num_slots"] * family.state_slot_bytes(cfg)
    pages = (engine["kv_pages"] * engine["kv_page_size"]
             * family.kv_pool_layers(cfg) * family.kv_token_layer_bytes(cfg))
    assert round(state / 1e9, 2) == 1.39 and round(pages / 1e9, 2) == 0.62
    # what the chip's memory must hold before a step's temporaries: a
    # quarter of its 16 GB and more, under it all
    assert 0.25 * 16e9 < nbytes + state + pages < 12.2e9
    for said in ("1.39 GB", "0.62 GB", "544 pages", "12.2-13.2 GB"):
        assert said in engine["kv_pool_arithmetic"], said


def test_the_parent_class_refuses_the_configuration_at_once():
    """What the parent commit does with the file: no family names
    `kimi_linear`, so the config is read as Mixtral's (it has
    `num_experts`), whose record lists none of its mechanisms — refused by
    name in `config_from_hf`, never built."""
    from llmlb_tpu import models
    from llmlb_tpu.models import kimi_linear

    hf = {k: v for k, v in CONFIG.items() if not isinstance(v, dict)
          or k in ("linear_attn_config", "expert_parallel")}
    was = models._BY_MODEL_TYPE.pop("kimi_linear")
    try:
        with pytest.raises(ValueError, match="does not compute"):
            models.config_from_hf(hf)
    finally:
        models._BY_MODEL_TYPE["kimi_linear"] = was
    assert was is kimi_linear and models.config_from_hf(hf)


def test_the_reference_is_found_by_the_configurations_name():
    from benchmark import reference

    module = reference.module_for(CONFIG)
    assert module.__name__.endswith("kimi_linear")
    assert module.FOLLOWS == "routing"
    plan = module.layer_plan(CONFIG)
    assert len(plan) == 27 and plan[0] == ("r0_", 0, True, False)
    assert [at for at, p in enumerate(plan) if not p[2]] == [
        3, 7, 11, 15, 19, 23, 26]
    assert plan[-1] == ("r14_", 0, False, True) and plan[6][:2] == ("r3_", 2)


def test_roofline_accounts_on_the_issues_numbers():
    hf = CONFIG
    assert ROOFLINE.is_kda(hf) and not ROOFLINE.is_kda({"model_type": "x"})
    assert (ROOFLINE.kda_layers(hf), ROOFLINE.latent_layers(hf),
            ROOFLINE.moe_layers(hf)) == (20, 7, 26)
    assert ROOFLINE.state_elements(hf) == STATE
    assert ROOFLINE.conv_channels(hf) == 12288
    assert ROOFLINE.expert_params(hf) == EXPERT
    assert ROOFLINE.held_slots(hf) == 26 * 16
    # the state's bytes are 2 x rows x H K V x 4 whatever kernel moves them
    w = ROOFLINE.step_call(hf, rows=32)
    assert w["bytes"] == 32 * (2 * STATE * 4 + (5 * 4096 + 32) * 4)
    assert w["flops"] == 7 * 32 * STATE
    assert round(32 * 20 * 2 * STATE * 4 / 1e9, 2) == 2.68  # the issue's
    # a latent cell is 512 + 64 numbers, read once for all 32 heads
    w = ROOFLINE.latent_decode(hf, cells=1000, rows=0)
    assert w["bytes"] == 1000 * 576 * 2
    assert w["flops"] == 2 * 1000 * 32 * (2 * 512 + 64)
    w = ROOFLINE.held_experts(hf, experts_touched=10, assignments=16)
    assert w["bytes"] == 10 * EXPERT * 2 + 16 * (2 * 2304 + 3 * 1024) * 2
    assert w["flops"] == 16 * 2 * EXPERT
    # the whole step at the issue's point: 32 rows, contexts of 350, 10.2
    # held experts touched a layer
    touched = 26 * 10.2
    w = ROOFLINE.decode_step(hf, ENGINE, live_tokens=32 * 350, rows=32,
                             experts_touched=touched)
    embed = 163840 * 2304
    weights = (ENGINE["param_bytes"] - 2 * embed
               - (26 * 16 - touched) * EXPERT * 2)
    state = 32 * 20 * (2 * STATE * 4 + 2 * 3 * 12288 * 2)
    cache = 32 * 350 * 7 * 576 * 2 + 32 * 7 * 32 * (2 * 512 + 64) * 2
    assert w["state_bytes"] == state
    assert w["bytes"] == pytest.approx(weights + state + cache)
    assert 9.5e9 < w["bytes"] < 10.1e9  # the issue's 9.8 GB
    assert 0.26 < w["state_bytes"] / w["bytes"] < 0.30
    # 12 ms at the published bandwidth
    assert 0.0115 < w["bytes"] / V5E["hbm_bytes_per_s"] < 0.0125
    # it grows with the rows and the context, not with what is not touched
    long = ROOFLINE.decode_step(hf, ENGINE, live_tokens=32 * 2000, rows=32,
                                experts_touched=touched)
    assert long["state_bytes"] == w["state_bytes"]
    assert long["bytes"] - w["bytes"] == 32 * 1650 * 7 * 576 * 2


def decode_record(ts, *, rows=32, burst=8, context=350, touched=265,
                  held=416):
    return {"kind": "decode", "ts": ts, "total_s": 0.12, "active_slots": rows,
            "tokens": rows * burst, "state_rows": rows * burst,
            "global_kv_tokens": rows * burst * 7 * context,
            "experts_touched": burst * touched,
            "expert_assignments": burst * held,
            "assignments_elsewhere": burst * (rows * 26 * 8 - held),
            "expert_load_max": 4}


def collected(steps, trace=None, config=CONFIG):
    return {"config": config, "steps": steps, "trace": trace, "peaks": V5E,
            "seconds": 51, "settings": SETTINGS, "requests": [],
            "engine": ENGINE}


def read(name, c):
    return mf.load_module("layer_metrics", name).read(c)


def traced(step_s=1.7e-4, latent_s=3e-5, expert_s=8e-5):
    """8 steps of a burst: 20 rule steps, 7 latent attentions and 26
    mixtures of three products each."""
    return {"wall_start": 99.0, "wall_stop": 107.0, "device_planes": 1,
            "ops": {"kda_step_f32_20_32_128_4096_":
                    {"time_s": 160 * step_s, "count": 160},
                    "paged_latent_decode_bf16_32_32_512_":
                    {"time_s": 56 * latent_s, "count": 56},
                    "grouped_expert_matmul_bf16_128_1024_":
                    {"time_s": 416 * expert_s, "count": 416},
                    "grouped_expert_matmul_f32_128_2304_":
                    {"time_s": 208 * expert_s, "count": 208},
                    "delta_rule_step_f32_12_32_96_5760_":  # another kernel's
                    {"time_s": 7.0, "count": 1},
                    "fusion_bf16_32_8192_": {"time_s": 9.0, "count": 1}},
            "modules": {"jit_many(123)": {"count": 8, "time_s": 0.96,
                                          "median_s": 0.12}}}


def test_readers_on_hand_worked_numbers():
    steps = [decode_record(100.1),  # in the traced part
             decode_record(90.0, rows=16, context=600, touched=200,
                           held=208)]  # before it
    c = collected(steps, traced())
    bw = V5E["hbm_bytes_per_s"]
    # the rule's kernel: 8 steps x 20 layers x 32 rows against 160 calls
    w = ROOFLINE.step_call(CONFIG, rows=8 * 32 * 20)
    assert read("kernel.kda_step_roofline", c) == pytest.approx(
        100 * w["bytes"] / bw / (160 * 1.7e-4))
    assert 60 < read("kernel.kda_step_roofline", c) < 100
    # the latent kernel: the traced record's cells alone
    w = ROOFLINE.latent_decode(CONFIG, cells=8 * 32 * 7 * 350,
                               rows=8 * 32 * 7)
    assert read("kernel.kda_latent_decode_roofline", c) == pytest.approx(
        100 * w["bytes"] / bw / (56 * 3e-5))
    # the grouped products: the traced record's touched and assignments
    w = ROOFLINE.held_experts(CONFIG, experts_touched=8 * 265,
                              assignments=8 * 416)
    assert read("kernel.kda_held_experts_roofline", c) == pytest.approx(
        100 * w["bytes"] / bw / (624 * 8e-5))
    # the whole step against the module's median over the burst
    w = ROOFLINE.decode_step(CONFIG, ENGINE, live_tokens=32 * 350, rows=32,
                             experts_touched=265)
    assert read("model.kda_moe_decode_roofline", c) == pytest.approx(
        100 * w["bytes"] / bw / (0.12 / 8))
    assert 70 < read("model.kda_moe_decode_roofline", c) < 100
    # the counters' readers take the window's decode records, traced or not
    both = ROOFLINE.decode_step(
        CONFIG, ENGINE, live_tokens=(32 * 350 + 16 * 600) / 2, rows=24,
        experts_touched=(265 + 200) / 2)
    assert read("linear.kda_state_bytes_share", c) == pytest.approx(
        100 * both["state_bytes"] / both["bytes"])
    assert read("moe.kda_held_assignment_share", c) == pytest.approx(
        100 * (416 + 208) / ((32 + 16) * 26 * 8))
    assert read("moe.kda_held_assignment_share", c) == pytest.approx(6.25)
    for name in READERS:
        assert 1 < read(name, c) <= 100, name


@pytest.mark.parametrize("held", [0.5, 1.0, 1.5])
def test_a_trace_that_holds_other_steps_than_the_records_moves_no_share(held):
    """A trace whose kernels ran `held` times as long for `held` times the
    records reads the same shares: work and time come from the same part of
    the window."""
    n = int(2 * held)
    steps = [decode_record(100.0 + i) for i in range(n)]
    tr = traced()
    tr["ops"] = {k: {"time_s": v["time_s"] * n, "count": v["count"] * n}
                 for k, v in tr["ops"].items()}
    one = collected([decode_record(100.0)], traced())
    many = collected(steps, tr)
    for name in READERS:
        assert read(name, many) == pytest.approx(read(name, one)), name


@pytest.mark.parametrize("name", READERS)
def test_readers_give_nothing_for_a_program_without_the_counters(name):
    """The parent commit, and every other family: step records without the
    counters, a trace without the kernels, another configuration. Nothing,
    and no exception."""
    plain = [{"kind": "decode", "ts": 100.0, "total_s": 0.2, "tokens": 256,
              "active_slots": 32}]
    linear = [{**plain[0], "state_rows": 256,
               "global_kv_tokens": 9000}]  # a dense delta-rule hybrid's
    trace = traced()
    assert read(name, collected(plain, trace)) is None
    assert read(name, collected(linear, trace)) is None
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
         "--manifest", REHEARSAL, "--workload", "tiny-kda.closed",
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
    assert split["correctness"]["router_rel_rms_err"] < 1e-5
    assert split["correctness"]["dropped_assignments"] == 0
    assert split["compiles_in_window"] == 0
    metrics = line["metrics"]
    assert 5 <= metrics["linear.kda_state_bytes_share"]["value"] <= 60
    # 4 of 8 experts held: about a half of the assignments
    assert 30 <= metrics["moe.kda_held_assignment_share"]["value"] <= 70
    assert metrics["engine.programs_built_in_window"]["value"] == 0
    # device-trace readers find no device plane on the CPU: left out
    assert not set(metrics) & set(READERS[:4])
    with open(os.path.join(mf.ROOT, ".bench_run", "tiny-kda.closed",
                           "last_run.json")) as f:
        steps = json.load(f)["steps"]
    decodes = [r for r in steps if r["kind"] == "decode"]
    assert decodes
    for r in decodes:  # every live row moved, in each of the five KDA layers
        assert r["state_rows"] == r["tokens"]
        assert r["global_kv_tokens"] >= r["tokens"] * 2 * 8
        assert (r["expert_assignments"] + r["assignments_elsewhere"]
                == 6 * 2 * r["tokens"])
        assert r["experts_touched"] <= 6 * 4 * (
            r["tokens"] // r["active_slots"])
    assert any(r["state_rows"] and r["expert_load_max"] >= 1
               for r in steps if r["kind"] == "prefill")


# --- benchmark/check_kda.py: the controls of what is new ---------------------

def _checked(cases, capsys, monkeypatch, seed="5"):
    from benchmark import check_kda

    rehearsal = os.path.dirname(REHEARSAL)
    monkeypatch.setattr(sys, "argv", [
        "check_kda.py", "--config",
        os.path.join(rehearsal, "configs", "debug-kimi-linear-tiny.json"),
        "--base", rehearsal, "--seeds", seed, "--cases", cases])
    monkeypatch.setattr(check_kda, "ROOT", tempfile.mkdtemp())  # its log
    monkeypatch.setenv("LLMLB_INIT_TIMEOUT", "0")  # no watchdog under capsys
    assert check_kda.main() == 0
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()
             if line.startswith("{")]
    return {line["case"]: line for line in lines}


def test_the_sound_program_passes_and_every_control_is_refused(capsys,
                                                               monkeypatch):
    from benchmark import check_kda

    got = _checked(check_kda.CASES, capsys, monkeypatch)
    assert set(got) == set(check_kda.CASES.split(","))
    for case in ("program", "interleaved_decode"):
        sound = got[case]["result"]
        assert sound["ok"] is True and sound["max_rel_rms_err"] < 5e-5, case
    # `live` false left the state where it was, to the last digit
    assert (got["interleaved_decode"]["result"]["max_rel_rms_err"]
            == got["program"]["result"]["max_rel_rms_err"])
    for case in ("live_mask_off", "int8_weights", "decay_channel_mean",
                 "beta_doubled", "keys_rotated", "conv_not_carried",
                 "zeroed_chosen_expert"):
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
    assert got["zeroed_chosen_expert"]["zeroed"][0] == "r1_we_down"
    # float32's state rounded to bf16 after every call shows on the CPU
    assert got["state_bf16"]["result"]["max_rel_rms_err"] > 1e-4
    # the control that tells KDA from a decay a head fails by a wide margin
    assert got["decay_channel_mean"]["result"]["max_rel_rms_err"] > 0.01


def test_the_precision_control_leaves_the_true_weights_behind(capsys,
                                                              monkeypatch):
    got = _checked("int8_weights,program", capsys, monkeypatch, seed="7")
    assert got["int8_weights"]["result"]["ok"] is False
    assert got["program"]["result"]["ok"] is True
    assert (got["program"]["result"]["max_rel_rms_err"] < 5e-5
            < got["int8_weights"]["result"]["max_rel_rms_err"])
