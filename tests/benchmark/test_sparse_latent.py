"""dots3-note-prev's files in the benchmark (PR 64): the manifest's new
entries against ISSUE 64's cell letter for letter, the configuration against
the catalog row it holds key for key but its four cuts, the operations and
bytes of benchmark/roofline/sparse_latent.py on a hand count at the
published widths, the eight readers on hand-worked numbers — and on a trace
that holds other steps than the records, which must not move them —, what
the readers give a program that has no such counters (nothing),
benchmark/check_sparse.py and its controls at a CI size, and the new cell's
path end to end on the CPU (`run.py --rehearse`).

Every assertion about `BENCHMARK.json` is of MEMBERSHIP and CONTENT, found
by name, never of position or of how many cells or configurations there
are: the next PR appends, and these tests must not turn red for it
(`test_hybrid_moe.py` asserts `MANIFEST["workloads"][-1]` and has been red
since the PR after its own)."""

import json
import os
import subprocess
import sys
import tempfile

import pytest

from benchmark import manifest as mf
from benchmark import peaks

MANIFEST = mf.load()
NAME = "dots3-note-prev-l5"
CELL = NAME + ".long-doc-notes"
CONFIG = mf.load_config(MANIFEST, NAME)
ROOFLINE = mf.load_module("roofline", "sparse_latent")
HERE = os.path.dirname(os.path.abspath(__file__))
REHEARSAL = os.path.join(HERE, "rehearsal_sparse", "BENCHMARK.json")
V5E = peaks.peaks_for("TPU v5 lite")
LAYER = {
    "model.sparse_latent_decode_roofline": ("model step", "device_trace", "%"),
    "kernel.sparse_index_select_roofline": ("kernels", "device_trace", "%"),
    "kernel.sparse_latent_decode_roofline": ("kernels", "device_trace", "%"),
    "kernel.sparse_window_latent_decode_roofline": ("kernels",
                                                    "device_trace", "%"),
    "kernel.sparse_held_experts_roofline": ("kernels", "device_trace", "%"),
    "model.sparse_extend_tok_per_s": ("model step", "device_trace", "tok/s"),
    "attn.sparse_selected_share": ("model step", "program_counter", "%"),
    "moe.sparse_held_assignment_share": ("model step", "program_counter",
                                         "%")}
READERS = tuple(LAYER)
ROOFLINES = READERS[:5]
SOURCE = ("https://huggingface.co/dots-studio/dots3-note-prev/blob/main/"
          "config.json")
CUTS = ["num_hidden_layers", "layer_types", "n_routed_experts", "vocab_size"]
N_PARAMS = 4_087_154_176  # the issue's 4,087 M
EXPERT = 3 * 5120 * 1536  # one expert's three matrices
# the choice bias and the index key's LayerNorm bias are float32
ENGINE = {"decode_burst": 8, "n_params": N_PARAMS,
          "param_bytes": 2 * N_PARAMS + 2 * (4 * 256 + 2 * 128)}

with open(os.path.join(mf.HERE, "settings.json")) as f:
    SETTINGS = json.load(f)


def test_the_manifests_new_entries_are_sound_and_are_the_issues():
    assert mf.check(MANIFEST) == []
    cell = mf.cell(MANIFEST, CELL)
    assert cell == {**cell, "chips": 1, "config": NAME,
                    "traffic": "long-doc-notes"}
    # ISSUE 64's rule applied: at 12,288-16,384 a window sampled 12 requests,
    # under the 24 it asks for, so the range is the one it names for that
    for said in ("closed loop", "16 callers", "8,192-12,288", "issue's rule",
                 "1,024 out", "chunks of 512", "2,048", "0.5 a held expert",
                 "deployment 4", "mixers 8x"):
        assert said in cell["why"], said
    assert len(cell["why"]) <= 200
    assert [w["name"] for w in MANIFEST["workloads"]
            if w["config"] == NAME] == [CELL]  # no second cell
    entry = next(c for c in MANIFEST["configs"] if c["name"] == NAME)
    assert entry == {**entry, "source": SOURCE, "reduced": CUTS,
                     "file": f"benchmark/configs/{NAME}.json"}
    traffic = mf.load_traffic("long-doc-notes")
    assert {k: traffic[k] for k in (
        "generator", "clients", "prompt", "max_tokens", "ramp_s",
        "start_after_tokens", "requests_per_client", "max_prefill_group")} == {
        "generator": "closed_loop", "clients": 16,
        "prompt": {"kind": "uniform", "lo": 8192, "hi": 12288},
        "max_tokens": 1024, "ramp_s": 40, "start_after_tokens": 2,
        "requests_per_client": 4, "max_prefill_group": 1}
    assert "12,288-16,384" in traffic["why"] and "rule" in traffic["why"]
    # a slot a caller, and a slot holds the longest request of the issue's
    # first range whole (the rule changes the prompts and nothing else)
    engine = CONFIG["engine"]
    assert engine["num_slots"] == traffic["clients"]
    assert engine["slot_capacity"] == 16384 + 1024
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name, (layer, source, unit) in LAYER.items():
        assert by_name[name] == {
            "name": name, "unit": unit, "better": "higher", "source": source,
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
                                     "device.idle_share",
                                     "device.hbm_peak_bytes",
                                     "engine.compiles_in_window"}
    assert {m["name"] for m in mf.metrics_for(MANIFEST, "end_to_end", CELL)
            } == {"tpot_p50_s", "setup_s"}


def test_the_configuration_holds_the_published_keys_and_its_four_cuts():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "dots3-note-prev")
    assert row["source_url"] == SOURCE == CONFIG["source"]
    differs = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
    assert differs == set(CUTS) == set(CONFIG["reduced"])
    assert list(CONFIG["reduced"]) == CUTS
    for key, (published, here) in {
            "num_hidden_layers": (46, 5), "n_routed_experts": (256, 32),
            "vocab_size": (152064, 19008)}.items():
        cut = CONFIG["reduced"][key]
        assert (cut["published"], cut["here"]) == (published, here) == (
            row["config"][key], CONFIG[key])
    assert CONFIG["layer_types"] == row["config"]["layer_types"][:5] == (
        ["full_attention"] * 2 + ["sliding_attention"] * 3)
    assert CONFIG["expert_parallel"] == {"chips": 8, "chip": 0,
                                         "experts": 256}
    for said in ("apply_mla_qkv_lora_rescale", "attention_gate_type",
                 "indexer", "rope", "sliding_window_size", "route",
                 "e_score_correction_bias", "weights", "left_out"):
        assert said in CONFIG["assumed"], said
    for said in ("TIES TO THE LOWER POSITION", "Hadamard", "FP8", "bf16"):
        assert said in CONFIG["assumed"]["indexer"], said
    for said in ("8 v5e chips", "32 of the 256", "chip 0", "eighths",
                 "pipeline"):
        assert said in CONFIG["deployment"], said
    assert "4,087.15 M parameters = 8.174 GB" in CONFIG["bytes"]
    assert CONFIG["engine"] == {**CONFIG["engine"], "num_slots": 16,
                                "slot_capacity": 17408, "kv_page_size": 128,
                                "kv_pages": 2200, "decode_burst": 8,
                                "prefix_cache": False}
    assert CONFIG["engine"]["prefill_buckets"][-1] == 512
    spec = CONFIG["correctness"]
    assert (spec["reference"], spec["prefill_tokens"], spec["extend_chunks"],
            spec["extend_tokens"], spec["decode_steps"]) == (
        "dots3_note", 512, 11, 512, 16)
    for key in ("tolerance", "router_tolerance", "flip_margin_multiple"):
        assert spec[key] > 0 and key in spec["why"], key


def test_the_program_reads_the_configuration_as_pages_with_index_keys_and_rings():
    import jax

    from benchmark import launcher
    from llmlb_tpu.models import dots3_note, family_for

    cfg = launcher.build_cfg(CONFIG)
    family = family_for(cfg)
    assert family is dots3_note
    assert cfg.held_experts == (0, 32) and cfg.router_experts == 256
    shapes = jax.eval_shape(lambda k: family.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    n = sum(v.size for v in shapes.values())
    nbytes = sum(v.size * v.dtype.itemsize for v in shapes.values())
    assert (n, nbytes) == (N_PARAMS, ENGINE["param_bytes"])
    assert 8.07e9 < nbytes < 8.27e9  # the issue's 8.17 GB +- 0.1
    engine = CONFIG["engine"]
    rings = (engine["num_slots"] + 1) * family.state_slot_bytes(cfg)
    pages = (engine["kv_pages"] * engine["kv_page_size"]
             * family.kv_pool_layers(cfg) * family.kv_token_layer_bytes(cfg))
    assert round(rings / 1e9, 3) == 0.075 and round(pages / 1e9, 3) == 0.865
    # the pages hold every slot's longest request, each slot's starting page
    # and the trash page
    assert engine["kv_pages"] >= 16 * (17408 // 128) + 16 + 1
    # what the chip's memory must hold before a step's temporaries: over a
    # quarter of its 16 GB, and under it all
    assert 0.25 * 16e9 < nbytes + rings + pages < 9.2e9
    for said in ("1,536 B", "2,200 pages = 0.865 GB", "0.075 GB",
                 "10-11 GB"):
        assert said in engine["kv_pool_arithmetic"], said


def test_the_parent_class_refuses_the_configuration_at_once():
    """What the parent commit does with the file: no family names
    `dots3_note`, so the config is read as a Llama-shaped dense decoder,
    whose record lists none of its mechanisms — refused by name in
    `config_from_hf`, never built."""
    from llmlb_tpu import models
    from llmlb_tpu.models import dots3_note

    hf = {k: v for k, v in CONFIG.items() if not isinstance(v, dict)
          or k == "expert_parallel"}
    was = models._BY_MODEL_TYPE.pop("dots3_note")
    try:
        with pytest.raises(ValueError, match="does not compute"):
            models.config_from_hf(hf)
    finally:
        models._BY_MODEL_TYPE["dots3_note"] = was
    assert was is dots3_note and models.config_from_hf(hf)


def test_the_reference_is_found_by_the_configurations_name():
    from benchmark import reference

    module = reference.module_for(CONFIG)
    assert module.__name__.endswith("dots3_note")
    assert module.FOLLOWS == "routing"
    assert module.layer_plan(CONFIG) == [
        ("r0_", 0, True, False), ("r1_", 0, True, True),
        ("r2_", 0, False, True), ("r2_", 1, False, True),
        ("r2_", 2, False, True)]
    # 6,160 tokens are taken 220 queries at a time
    assert module.query_blocks(512 + 11 * 512 + 16) == 220
    full, sliding = (module.mixer_dims(CONFIG, kind) for kind in (True, False))
    assert (full["heads"], full["rank"], full["nope"], full["theta"],
            full["top_k"], full["index_heads"]) == (128, 512, 128, 8e7, 2048,
                                                    64)
    assert (sliding["heads"], sliding["rank"], sliding["nope"],
            sliding["theta"], sliding["window"]) == (64, 1024, 192, 5e4, 513)
    assert full["q_scale"] == sliding["kv_scale"] == 5 ** 0.5
    assert full["kv_scale"] == 10 ** 0.5


def test_roofline_accounts_on_a_hand_count_at_the_published_widths():
    hf = CONFIG
    assert ROOFLINE.is_sparse(hf) and not ROOFLINE.is_sparse(
        {"model_type": "deepseek_v3"})
    assert (ROOFLINE.layers(hf, ROOFLINE.FULL),
            ROOFLINE.layers(hf, ROOFLINE.SLIDING),
            ROOFLINE.moe_layers(hf)) == (2, 3, 4)
    assert ROOFLINE.expert_params(hf) == EXPERT
    assert ROOFLINE.held_slots(hf) == 4 * 32
    # an index key is 128 numbers, read once for all 64 index heads; the
    # score the top-k reads a float a cell
    w = ROOFLINE.index_select(hf, cells=1000, rows=0)
    assert w["bytes"] == 1000 * 128 * 2 + 4 * 1000
    assert w["flops"] == 1000 * 64 * (2 * 128 + 3)
    assert ROOFLINE.index_select(hf, cells=0, rows=1)["bytes"] == 64 * 129 * 2
    # a chosen cell is 512 + 64 numbers, read once for all 128 heads
    w = ROOFLINE.sparse_decode(hf, cells=2048, rows=1)
    assert w["bytes"] == 2048 * 576 * 2 + 128 * (2 * 512 + 64) * 2
    assert w["flops"] == 2 * 2048 * 128 * (2 * 512 + 64)
    # a ring cell is 1024 + 64 numbers, for 64 heads
    w = ROOFLINE.window_decode(hf, cells=513, rows=0)
    assert w["bytes"] == 513 * 1088 * 2
    assert w["flops"] == 2 * 513 * 64 * (2 * 1024 + 64)
    w = ROOFLINE.held_experts(hf, experts_touched=12.7, assignments=16)
    assert w["bytes"] == 12.7 * EXPERT * 2 + 16 * (2 * 5120 + 3 * 1536) * 2
    assert w["flops"] == 16 * 2 * EXPERT
    # the whole step at the issue's point: 16 rows at contexts of 15k, 12.7
    # of the 32 held experts touched a layer
    touched = 4 * 12.7
    args = dict(scored_cells=2 * 16 * 15000, selected_cells=2 * 16 * 2048,
                window_cells=3 * 16 * 513, rows=16, experts_touched=touched)
    w = ROOFLINE.decode_step(hf, ENGINE, **args)
    embed = 19008 * 5120
    weights = (ENGINE["param_bytes"] - 2 * embed
               - (4 * 32 - touched) * EXPERT * 2)
    index = 2 * 16 * 15000 * (128 * 2 + 4) + 2 * 16 * 64 * 129 * 2
    chosen = 2 * 16 * (2048 * 576 + 128 * 1088) * 2
    rings = 3 * 16 * (513 * 1088 + 64 * 2112) * 2
    assert w["bytes"] == pytest.approx(weights + index + chosen + rings)
    # the issue's 4.3 GB of weights and 0.2 GB of chosen cells and index keys
    assert 4.2e9 < weights < 4.5e9
    assert 0.19e9 < index + chosen < 0.22e9
    # 5.6 ms at the published bandwidth: the issue's "about 6 ms"
    assert 0.0052 < w["bytes"] / V5E["hbm_bytes_per_s"] < 0.0062
    # a longer context adds index keys alone: the chosen cells stay 2,048
    long = ROOFLINE.decode_step(hf, ENGINE, **{
        **args, "scored_cells": 2 * 16 * 17000})
    assert long["bytes"] - w["bytes"] == 2 * 16 * 2000 * (128 * 2 + 4)
    # a masked whole read is 0.6 GB a step, three times the mechanism's
    assert 0.5e9 < 2 * 16 * 15000 * (512 + 128 + 128) * 2 < 0.6e9 + 1.5e8


def decode_record(ts, *, rows=16, burst=8, context=15000, touched=50,
                  held=64):
    return {"kind": "decode", "ts": ts, "total_s": 0.08, "active_slots": rows,
            "tokens": rows * burst,
            "index_scored_cells": rows * burst * 2 * context,
            "index_selected_cells": rows * burst * 2 * min(context, 2048),
            "window_kv_tokens": rows * burst * 3 * min(context, 513),
            "experts_touched": burst * touched,
            "expert_assignments": burst * held,
            "assignments_elsewhere": burst * (rows * 4 * 8 - held),
            "expert_load_max": 3}


def prefill_record(ts, tokens=512):
    return {"kind": "prefill", "ts": ts, "total_s": 0.05, "active_slots": 1,
            "tokens": tokens, "experts_touched": 128,
            "expert_assignments": tokens, "assignments_elsewhere": 7 * tokens,
            "expert_load_max": 40}


def collected(steps, trace=None, config=CONFIG):
    return {"config": config, "steps": steps, "trace": trace, "peaks": V5E,
            "seconds": 51, "settings": SETTINGS, "requests": [],
            "engine": ENGINE}


def read(name, c):
    return mf.load_module("layer_metrics", name).read(c)


def traced(index_s=2e-4, sparse_s=1.2e-3, window_s=4e-5, expert_s=1.9e-3):
    """8 steps of a burst: 2 index scorings, 2 sparse attentions, 3 ring
    attentions and 4 mixtures of one product each, and two extend chunks."""
    return {"wall_start": 99.0, "wall_stop": 107.0, "device_planes": 1,
            "ops": {"index_scores_decode_f32_16_17_1_1024_":
                    {"time_s": 16 * index_s, "count": 16},
                    "sparse_latent_decode_bf16_16_128_512_":
                    {"time_s": 16 * sparse_s, "count": 16},
                    "window_latent_decode_bf16_16_64_1024_":
                    {"time_s": 24 * window_s, "count": 24},
                    "grouped_expert_matmul_bf16_16_5120_":
                    {"time_s": 32 * expert_s, "count": 32},
                    "paged_latent_decode_bf16_32_32_512_":  # another kernel's
                    {"time_s": 7.0, "count": 1},
                    "fusion_bf16_16_8192_": {"time_s": 9.0, "count": 1}},
            "modules": {"jit_many(123)": {"count": 8, "time_s": 0.64,
                                          "median_s": 0.08,
                                          "durations_s": [0.08] * 8},
                        "jit_prefill_extend_pages(7)": {
                            "count": 2, "time_s": 0.09, "median_s": 0.045,
                            "durations_s": [0.045] * 2}}}


def test_readers_on_hand_worked_numbers():
    steps = [decode_record(100.1),  # in the traced part
             prefill_record(101.0), prefill_record(102.0),
             decode_record(90.0, rows=8, context=1000, touched=30,
                           held=32)]  # before it, under the top-k
    c = collected(steps, traced())
    bw = V5E["hbm_bytes_per_s"]

    def memory_share(w, seconds):
        return 100 * w["bytes"] / bw / seconds

    # the score kernel: 8 steps x 16 rows x 2 layers x 15k cells, 16 calls
    w = ROOFLINE.index_select(CONFIG, cells=8 * 16 * 2 * 15000,
                              rows=8 * 16 * 2)
    assert read("kernel.sparse_index_select_roofline", c) == pytest.approx(
        memory_share(w, 16 * 2e-4))
    # the sparse kernel: the CHOSEN cells of the traced record alone, so a
    # kernel that reads 15k cells a row for 2,048 chosen reads a low share
    w = ROOFLINE.sparse_decode(CONFIG, cells=8 * 16 * 2 * 2048,
                               rows=8 * 16 * 2)
    assert read("kernel.sparse_latent_decode_roofline", c) == pytest.approx(
        memory_share(w, 16 * 1.2e-3))
    assert read("kernel.sparse_latent_decode_roofline", c) < 25
    w = ROOFLINE.window_decode(CONFIG, cells=8 * 16 * 3 * 513,
                               rows=8 * 16 * 3)
    assert read("kernel.sparse_window_latent_decode_roofline",
                c) == pytest.approx(memory_share(w, 24 * 4e-5))
    # the grouped products: the traced records' touched and assignments,
    # the two chunks' among them (bound by their operations)
    w = ROOFLINE.held_experts(CONFIG, experts_touched=8 * 50 + 2 * 128,
                              assignments=8 * 64 + 2 * 512)
    assert read("kernel.sparse_held_experts_roofline", c) == pytest.approx(
        memory_share(w, 32 * 1.9e-3))
    # the whole step against the module's median over the burst
    w = ROOFLINE.decode_step(
        CONFIG, ENGINE, scored_cells=16 * 2 * 15000,
        selected_cells=16 * 2 * 2048, window_cells=16 * 3 * 513, rows=16,
        experts_touched=50)
    assert read("model.sparse_latent_decode_roofline", c) == pytest.approx(
        memory_share(w, 0.08 / 8))
    assert 40 < read("model.sparse_latent_decode_roofline", c) < 100
    # the two chunks' tokens over the extend program's device time
    assert read("model.sparse_extend_tok_per_s", c) == pytest.approx(
        1024 / 0.09)
    # the counters' readers take the window's decode records, traced or not
    assert read("attn.sparse_selected_share", c) == pytest.approx(
        100 * (16 * 2048 + 8 * 1000) / (16 * 15000 + 8 * 1000))
    assert read("moe.sparse_held_assignment_share", c) == pytest.approx(
        100 * (64 + 32) / ((16 + 8) * 4 * 8))
    assert read("moe.sparse_held_assignment_share", c) == pytest.approx(12.5)
    for name in READERS:
        value = read(name, c)
        assert value > 1 and (value <= 100 or name.endswith("tok_per_s")), name


@pytest.mark.parametrize("held", [0.5, 1.0, 1.5])
def test_a_trace_that_holds_other_steps_than_the_records_moves_no_share(held):
    """A trace whose kernels ran `held` times as long for `held` times the
    records reads the same shares: work and time come from the same part of
    the window."""
    n = int(2 * held)
    steps = [r for i in range(n)
             for r in (decode_record(100.0 + i), prefill_record(100.5 + i))]
    tr = traced()
    tr["ops"] = {k: {"time_s": v["time_s"] * n, "count": v["count"] * n}
                 for k, v in tr["ops"].items()}
    tr["modules"] = {
        k: {**v, "time_s": v["time_s"] * n, "count": v["count"] * n,
            "durations_s": v["durations_s"] * n}
        for k, v in tr["modules"].items()}
    one = collected([decode_record(100.0), prefill_record(100.5)], traced())
    many = collected(steps, tr)
    for name in READERS:
        assert read(name, many) == pytest.approx(read(name, one)), name


@pytest.mark.parametrize("name", READERS)
def test_readers_give_nothing_for_a_program_without_the_counters(name):
    """The parent commit, and every other family: step records without the
    counters, a trace without the kernels, another configuration. Nothing,
    and no exception."""
    plain = [{"kind": "decode", "ts": 100.0, "total_s": 0.2, "tokens": 128,
              "active_slots": 16}]
    window = [{**plain[0], "window_kv_tokens": 900,
               "global_kv_tokens": 9000}]  # a window family's
    trace = traced()
    assert read(name, collected(plain, trace)) is None
    assert read(name, collected(window, trace)) is None
    assert read(name, collected([], None)) is None
    assert read(name, collected([], trace)) is None
    full = [decode_record(100.0), prefill_record(100.5)]
    for other in (c["name"] for c in MANIFEST["configs"] if c["name"] != NAME):
        c = collected(full, trace, mf.load_config(MANIFEST, other))
        assert read(name, c) is None, other
    if LAYER[name][1] == "device_trace":  # the records, and no kernel rows
        bare = {**trace, "ops": {}, "modules": {}}
        assert read(name, collected(full, bare)) is None


def test_the_cells_path_runs_end_to_end_on_the_cpu_at_a_ci_size():
    """`run.py --rehearse --trace 1` on the family through the real
    launcher, gateway and generator: `correct` holds prefill, two extends
    and the decode steps to the reference (which selects for itself) with
    the routing followed, every request is served, the counters are on the
    window's records and the counter readers in the line."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(mf.ROOT, "benchmark", "run.py"),
         "--manifest", REHEARSAL, "--workload", "tiny-sparse.closed",
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
    # contexts of 10-44 tokens against a top-k of 16
    assert 30 <= metrics["attn.sparse_selected_share"]["value"] < 100
    # 4 of 8 experts held: about a half of the assignments
    assert 30 <= metrics["moe.sparse_held_assignment_share"]["value"] <= 70
    assert metrics["engine.programs_built_in_window"]["value"] == 0
    # device-trace readers find no device plane on the CPU: left out
    assert not set(metrics) & set(READERS[:6])
    with open(os.path.join(mf.ROOT, ".bench_run", "tiny-sparse.closed",
                           "last_run.json")) as f:
        steps = json.load(f)["steps"]
    decodes = [r for r in steps if r["kind"] == "decode"]
    assert decodes
    for r in decodes:
        assert 0 < r["index_selected_cells"] <= min(
            r["index_scored_cells"], r["tokens"] * 2 * 16)
        assert 0 < r["window_kv_tokens"] <= r["tokens"] * 3 * 5
        assert (r["expert_assignments"] + r["assignments_elsewhere"]
                == 4 * 2 * r["tokens"])
    assert any(r["index_scored_cells"] > r["index_selected_cells"]
               for r in decodes)
    assert any(r["index_scored_cells"] and r["expert_load_max"] >= 1
               for r in steps if r["kind"] == "prefill")


# --- benchmark/check_sparse.py: the controls of what is new ------------------

def _checked(cases, capsys, monkeypatch, seed="5"):
    from benchmark import check_sparse

    rehearsal = os.path.dirname(REHEARSAL)
    monkeypatch.setattr(sys, "argv", [
        "check_sparse.py", "--config",
        os.path.join(rehearsal, "configs", "debug-dots3-note-tiny.json"),
        "--base", rehearsal, "--seeds", seed, "--cases", cases])
    monkeypatch.setattr(check_sparse, "ROOT", tempfile.mkdtemp())  # its log
    monkeypatch.setenv("LLMLB_INIT_TIMEOUT", "0")  # no watchdog under capsys
    assert check_sparse.main() == 0
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()
             if line.startswith("{")]
    return {line["case"]: line for line in lines}


def test_the_sound_program_passes_twice_and_the_precision_control_is_refused(
        capsys, monkeypatch):
    """The tool's own loop at a CI size (the structure controls are held to
    the comparison by tests/engine/test_sparse_family.py): the sound program
    read as `correct` reads it and with its selection followed, the
    selection's verdict beside it, the precision control refused and the
    true weights back behind it."""
    got = _checked("program,followed,dense_attention,int8_weights,unfollowed",
                   capsys, monkeypatch)
    for case in ("program", "followed", "unfollowed"):
        sound = got[case]["result"]
        assert sound["ok"] is True and sound["max_rel_rms_err"] < 5e-5, case
    verdict = got["followed"]["selection"]
    assert verdict["choice_is_own_topk"] is True
    assert verdict["index_rel_rms_err"] < 1e-5
    assert verdict["disagreeing_cells_max"] == 0
    for case in ("dense_attention", "int8_weights"):
        result = got[case]["result"]
        assert result["ok"] is False and "logits" in result["grounds"], case
        assert result["max_rel_rms_err"] > 1e-3, case
    # in float32 the program's choices ARE the reference's: nothing to follow
    assert got["unfollowed"]["result"]["flips"] == 0
    assert (got["unfollowed"]["result"]["max_rel_rms_err"]
            == got["program"]["result"]["max_rel_rms_err"])
