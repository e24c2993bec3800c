"""The dense state-space hybrid's files in the benchmark (PR 55): its
configuration against the catalog row it holds key for key with nothing
cut, the operations and bytes of benchmark/roofline/ssm_dense.py on the
issue's arithmetic, the four readers on hand-worked numbers — and on a trace
that holds other steps than the records, which must not move them —, what
the readers give a program that has no such counters (nothing),
benchmark/check_ssm_dense.py and its controls at a CI size, and the new
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
NAME = "granite-4.0-h-micro"
CELL = NAME + ".decode-saturated"
CONFIG = mf.load_config(MANIFEST, NAME)
ROOFLINE = mf.load_module("roofline", "ssm_dense")
HERE = os.path.dirname(os.path.abspath(__file__))
REHEARSAL = os.path.join(HERE, "rehearsal_ssm_dense", "BENCHMARK.json")
V5E = peaks.peaks_for("TPU v5 lite")
LAYER = {"model.ssm_dense_decode_roofline": ("model step", "device_trace"),
         "kernel.ssm_dense_step_roofline": ("kernels", "device_trace"),
         "kernel.ssm_dense_attn_decode_roofline": ("kernels", "device_trace"),
         "ssm.dense_state_bytes_share": ("model step", "program_counter")}
READERS = tuple(LAYER)
SOURCE = ("https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/"
          "config.json")
N_PARAMS = 3_191_396_096  # the issue's 3,191.4 M
STATE = 64 * 64 * 128  # one sequence's state in one layer, elements
ENGINE = {"decode_burst": 8, "param_bytes": 6_382_806_016,
          "n_params": N_PARAMS}

with open(os.path.join(mf.HERE, "settings.json")) as f:
    SETTINGS = json.load(f)


def test_the_manifest_is_sound_and_the_cell_is_the_issues():
    assert mf.check(MANIFEST) == []
    cell = mf.cell(MANIFEST, CELL)
    assert cell == {**cell, "chips": 1, "config": NAME,
                    "traffic": "decode-saturated"}
    for said in ("closed loop", "32 callers", "64-128", "512 out",
                 "36 state steps", "one group", "4 attentions",
                 "heads of 64", "whole"):
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


def test_the_configuration_holds_the_rows_keys_unchanged_and_nothing_cut():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == NAME)
    published = row["config"]
    assert row["source_url"] == SOURCE == CONFIG["source"]
    assert {k for k, v in published.items() if CONFIG.get(k, "-") != v
            } == set() == set(CONFIG["reduced"])
    entry = mf.config_entry(MANIFEST, NAME)
    assert entry["reduced"] == [] and CONFIG["reduced"] == {}
    assert entry["file"] == "benchmark/configs/granite-4.0-h-micro.json"
    assert entry["source"] == SOURCE and len(entry["why"]) <= 200
    assert (CONFIG["num_hidden_layers"], len(CONFIG["layer_types"])) == (40, 40)
    assert [i for i, kind in enumerate(CONFIG["layer_types"])
            if kind == "attention"] == [5, 15, 25, 35]
    assert CONFIG["torch_dtype"] == "bfloat16"
    assert set(CONFIG["assumed"]) >= {
        "head_dim", "rotary_embedding", "time_step_limit", "ssm_state_dtype",
        "weights", "feed_forward", "multipliers", "page_pool"}
    assert "64" in CONFIG["assumed"]["head_dim"]
    assert "float32" in CONFIG["assumed"]["ssm_state_dtype"]
    assert "whole" in CONFIG["deployment"]
    assert "one replica of 32 rows" in CONFIG["deployment"]
    correctness = CONFIG["correctness"]
    assert correctness["reference"] == "granite_hybrid"
    assert (correctness["prefill_tokens"], correctness["extend_chunks"],
            correctness["extend_tokens"], correctness["decode_steps"]) == (
        256, 2, 64, 16)
    for text in (correctness["why"], CONFIG["deployment"],
                 CONFIG["engine"]["kv_pool_arithmetic"],
                 *CONFIG["assumed"].values()):
        assert "TODO" not in text and "provisional" not in text.lower()
    for said in ("seeds", "int8", "residual", "1/8", "group", "decay",
                 "convolution", "live"):
        assert said in correctness["why"], said
    engine = CONFIG["engine"]
    assert (engine["num_slots"], engine["slot_capacity"],
            engine["kv_page_size"], engine["kv_pages"], engine["decode_burst"],
            engine["prefix_cache"]) == (32, 2048, 128, 544, 8, False)
    assert engine["prefill_buckets"] == [32, 64, 128, 256, 512]
    assert "3,191,396,096" in engine["kv_pool_arithmetic"]


def test_the_program_reads_the_configuration_as_pages_and_a_state_a_slot():
    import jax

    from benchmark import launcher
    from llmlb_tpu.engine.scheduler import kv_page_bytes
    from llmlb_tpu.models import family_for, granite_hybrid

    cfg = launcher.build_cfg(CONFIG)
    assert family_for(cfg) is granite_hybrid
    assert (cfg.num_layers, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_,
            cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state,
            cfg.conv_kernel, cfg.chunk_size, cfg.vocab_size) == (
        40, 32, 8, 64, 64, 64, 1, 128, 4, 256, 100352)
    record = granite_hybrid.FAMILY
    assert record.kv_pool_layers(cfg) == 4
    assert record.kv_token_layer_bytes(cfg) == 2 * 8 * 64 * 2
    assert kv_page_bytes(cfg, 128) == 4 * 128 * 2048 == 1_048_576
    assert 544 * 1_048_576 / 1e9 == pytest.approx(0.57, abs=5e-3)
    assert record.state_slot_bytes(cfg) == 36 * (STATE * 4 + 3 * 4352 * 2)
    assert 32 * record.state_slot_bytes(cfg) / 1e9 == pytest.approx(
        2.446, abs=1e-3)
    shapes = jax.eval_shape(lambda k: granite_hybrid.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    assert sum(v.size for v in shapes.values()) == N_PARAMS
    assert sum(v.size * v.dtype.itemsize for v in shapes.values()
               ) == ENGINE["param_bytes"]
    assert ENGINE["param_bytes"] / 1e9 == pytest.approx(6.38, abs=5e-3)
    pool = jax.eval_shape(lambda: granite_hybrid.init_kv_pages(
        cfg, 544, 128, num_slots=32))
    assert pool[0].pages.shape == (4, 544, 128, 4, 128)  # two heads a row
    assert pool[0].state.shape == (36, 32, 64, 64, 128)
    assert pool[1].state.shape == (36, 32, 3, 4352)


def test_the_parent_class_refuses_the_configuration_at_once():
    """What the tree before PR 55 does with the new cell: no family names
    `granitemoehybrid`, so the file is read for the Llama class, which
    refuses `layer_types` with `mamba` by name before anything is built."""
    from llmlb_tpu.models import config_from_hf

    with pytest.raises(ValueError, match="layer_types"):
        config_from_hf({**{k: v for k, v in CONFIG.items()
                           if not isinstance(v, dict)},
                        "model_type": "a_type_nobody_registered"})


def test_the_reference_is_found_by_the_configurations_name():
    from benchmark import reference

    module = reference.module_for(CONFIG)
    assert module.__name__.endswith("granite_hybrid")
    assert not hasattr(module, "FOLLOWS")  # nothing is routed
    with open(module.__file__) as f:
        source = f.read()
    # independent of the program's ops: no kernel, cache, batching or chunk
    assert "llmlb_tpu" not in source
    assert 'default_matmul_precision("highest")' in source
    assert "pallas" not in source and "ssd_chunked" not in source
    assert "jax.lax.scan(token" in source  # the state, token by token


def test_roofline_accounts_on_the_issues_numbers():
    assert ROOFLINE.state_elements(CONFIG) == STATE
    assert STATE * 4 == 2_097_152  # 2.097 MB a layer and slot
    assert (ROOFLINE.layers(CONFIG, "mamba"),
            ROOFLINE.layers(CONFIG, "attention")) == (36, 4)
    assert ROOFLINE.conv_channels(CONFIG) == 4352
    one = ROOFLINE.ssm_step_call(CONFIG, rows=1)
    assert one["bytes"] == 2 * STATE * 4 + (2 * 4096 + 64 + 2 * 128) * 2
    assert one["flops"] == 6 * STATE
    # 4.21 MB a (row, layer): 5.1 us at 819 GB/s, and memory-bound
    share, bound = peaks.roofline_share_pct(one["flops"], one["bytes"],
                                            5.2e-6, V5E)
    assert bound == "memory" and 98 < share < 100
    # a step at 32 rows and contexts of 350: the state 4.89 GB with the
    # convolution's rows, 43% of 11.4 GB
    w = ROOFLINE.decode_step(CONFIG, ENGINE, live_tokens=32 * 350, rows=32)
    assert w["state_bytes"] == 32 * 36 * (2 * STATE * 4 + 2 * 3 * 4352 * 2)
    assert w["state_bytes"] / 1e9 == pytest.approx(4.89, abs=0.01)
    attn = ROOFLINE.attn_decode_call(CONFIG, cells=32 * 350 * 4, rows=32 * 4)
    assert attn["bytes"] == 32 * 350 * 4 * 2048 + 128 * 2 * 2048 * 2
    assert attn["bytes"] / 1e9 == pytest.approx(0.092, abs=0.002)
    assert attn["flops"] == 4 * 32 * 350 * 4 * 2048
    # every weight, the table once: it is the head
    assert w["bytes"] == ENGINE["param_bytes"] + w["state_bytes"] + attn["bytes"]
    assert w["bytes"] / 1e9 == pytest.approx(11.37, abs=0.02)
    assert 100 * w["state_bytes"] / w["bytes"] == pytest.approx(43.0, abs=0.3)
    share, bound = peaks.roofline_share_pct(w["flops"], w["bytes"], 0.019, V5E)
    assert bound == "memory" and 72 < share < 74  # 13.9 ms of 19
    # the state's part grows with the rows, not with the context
    long = ROOFLINE.decode_step(CONFIG, ENGINE, live_tokens=32 * 2000, rows=32)
    assert long["state_bytes"] == w["state_bytes"]
    assert long["bytes"] - w["bytes"] == 32 * 1650 * 4 * 2048
    both = ROOFLINE.work(CONFIG, ENGINE, live_tokens=32 * 350, rows=32)
    assert both["decode_step"] == w
    assert both["ssm_step_call"] == ROOFLINE.ssm_step_call(CONFIG, rows=32)
    assert both["attn_decode_call"]["bytes"] == 32 * 350 * 2048 + 32 * 8192


def decode_record(ts, *, rows=32, burst=8, context=350):
    return {"kind": "decode", "ts": ts, "total_s": 0.15, "active_slots": rows,
            "tokens": rows * burst, "state_rows": rows * burst,
            "global_kv_tokens": rows * burst * 4 * context}


def collected(steps, trace=None, config=CONFIG):
    return {"config": config, "steps": steps, "trace": trace, "peaks": V5E,
            "seconds": 51, "settings": SETTINGS, "requests": [],
            "engine": ENGINE}


def read(name, c):
    return mf.load_module("layer_metrics", name).read(c)


def traced(ssm_calls=288, attn_calls=32):
    """8 steps of a burst: 36 state steps and 4 attentions each."""
    return {"wall_start": 99.0, "wall_stop": 107.0, "device_planes": 1,
            "ops": {"ssm_decode_step_f32_32_8_4096_":
                    {"time_s": ssm_calls * 2.6e-4, "count": ssm_calls},
                    "paged_flash_decode_bf16_32_32_128_":
                    {"time_s": attn_calls * 5e-5, "count": attn_calls},
                    "delta_rule_step_f32_32_1_5760_":  # another kernel's
                    {"time_s": 7.0, "count": 1},
                    "fusion_bf16_32_8192_": {"time_s": 9.0, "count": 1}},
            "modules": {"jit_many(123)": {"count": 8, "time_s": 1.2,
                                          "median_s": 0.152}}}


def test_readers_on_hand_worked_numbers():
    steps = [decode_record(100.1),  # in the traced part
             decode_record(90.0, rows=16, context=600),  # before it
             {"kind": "prefill", "ts": 100.3, "total_s": 0.05, "tokens": 700,
              "active_slots": 8, "state_rows": 8, "global_kv_tokens": 2800,
              "scan_tokens": 700, "scan_chunks": 8}]
    c = collected(steps, traced())
    # 288 calls of 32 rows in 74.9 ms: 5.14 of 8.1 us a (row, layer)
    w = ROOFLINE.ssm_step_call(CONFIG, rows=288 * 32)
    want, bound = peaks.roofline_share_pct(w["flops"], w["bytes"],
                                           288 * 2.6e-4, V5E)
    assert read("kernel.ssm_dense_step_roofline", c) == pytest.approx(want)
    assert 62 < want < 65 and bound == "memory"
    w = ROOFLINE.attn_decode_call(CONFIG, cells=32 * 32 * 350, rows=32 * 32)
    want, _ = peaks.roofline_share_pct(w["flops"], w["bytes"], 32 * 5e-5, V5E)
    assert read("kernel.ssm_dense_attn_decode_roofline", c
                ) == pytest.approx(want)
    assert 0 < want < 100
    w = ROOFLINE.decode_step(CONFIG, ENGINE, live_tokens=32 * 350, rows=32)
    want, _ = peaks.roofline_share_pct(w["flops"], w["bytes"], 0.152 / 8, V5E)
    assert read("model.ssm_dense_decode_roofline", c) == pytest.approx(want)
    assert 72 < want < 74
    # the state's share is over the whole window's decode records: 16 steps,
    # 24 rows a step and (32 x 350 + 16 x 600) / 2 tokens alive
    w = ROOFLINE.decode_step(CONFIG, ENGINE, live_tokens=10400, rows=24)
    assert read("ssm.dense_state_bytes_share", c) == pytest.approx(
        100 * w["state_bytes"] / w["bytes"])
    assert 35 < read("ssm.dense_state_bytes_share", c) < 38


@pytest.mark.parametrize("held", [0.5, 1.0, 1.6])
def test_a_trace_that_holds_other_steps_than_the_records_moves_no_share(held):
    """The ledger's PR 54 lines for `nemotron-…` read
    `kernel.ssm_decode_step_roofline` 98.6 | 61.5 on one program: its reader
    sums the records' rows over one stretch and the trace's time over
    another. Here the calls come from the trace's own rows: a trace that
    holds half the records' steps, or 1.6 times them, reads the same
    shares."""
    steps = [decode_record(100.1)]
    whole = collected(steps, traced())
    other = collected(steps, traced(ssm_calls=int(288 * held),
                                    attn_calls=int(32 * held)))
    for name in READERS[:3]:
        assert read(name, other) == pytest.approx(read(name, whole),
                                                  rel=1e-9), name
        assert read(name, other) < 100


@pytest.mark.parametrize("name", READERS)
def test_readers_give_nothing_for_a_program_without_the_counters(name):
    """The parent commit, and every other family: step records without the
    two counters, a trace without the kernel, another configuration.
    Nothing, and no exception."""
    plain = [{"kind": "decode", "ts": 100.0, "total_s": 0.2, "tokens": 256,
              "active_slots": 32}]
    hybrid = [{**plain[0], "state_rows": 256, "experts_touched": 900,
               "expert_assignments": 1500, "assignments_elsewhere": 1500,
               "expert_load_max": 9}]  # a routed state-space hybrid's record
    trace = traced()
    assert read(name, collected(plain, trace)) is None
    assert read(name, collected(hybrid, trace)) is None
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
    and the decode steps to the reference, every request is served, the two
    counters are on the window's records and the counter reader in the
    line."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(mf.ROOT, "benchmark", "run.py"),
         "--manifest", REHEARSAL, "--workload", "tiny-ssm-dense.closed",
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
    assert split["compiles_in_window"] == 0
    assert 5 <= line["metrics"]["ssm.dense_state_bytes_share"]["value"] <= 60
    assert line["metrics"]["engine.programs_built_in_window"]["value"] == 0
    # device-trace readers find no device plane on the CPU: left out
    assert not set(line["metrics"]) & set(READERS[:3])
    with open(os.path.join(mf.ROOT, ".bench_run", "tiny-ssm-dense.closed",
                           "last_run.json")) as f:
        steps = json.load(f)["steps"]
    decodes = [r for r in steps if r["kind"] == "decode"]
    assert decodes
    for r in decodes:  # every live row advanced, two attention layers
        assert r["state_rows"] == r["tokens"]
        assert r["global_kv_tokens"] >= r["tokens"] * 2 * 8
    assert any(r.get("scan_tokens") for r in steps if r["kind"] == "prefill")


# --- benchmark/check_ssm_dense.py: the controls of what is new ---------------

def _checked(cases, capsys, monkeypatch, seed="5"):
    from benchmark import check_ssm_dense

    rehearsal = os.path.dirname(REHEARSAL)
    monkeypatch.setattr(sys, "argv", [
        "check_ssm_dense.py", "--config",
        os.path.join(rehearsal, "configs", "debug-granite-hybrid-tiny.json"),
        "--base", rehearsal, "--seeds", seed, "--cases", cases])
    monkeypatch.setattr(check_ssm_dense, "ROOT", tempfile.mkdtemp())  # its log
    monkeypatch.setenv("LLMLB_INIT_TIMEOUT", "0")  # no watchdog under capsys
    assert check_ssm_dense.main() == 0
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()
             if line.startswith("{")]
    return {line["case"]: line for line in lines}


def test_the_sound_program_passes_and_every_control_is_refused(capsys,
                                                               monkeypatch):
    from benchmark import check_ssm_dense

    got = _checked(check_ssm_dense.CASES, capsys, monkeypatch)
    assert set(got) == set(check_ssm_dense.CASES.split(","))
    for case in ("program", "interleaved_decode"):
        sound = got[case]["result"]
        assert sound["ok"] is True and sound["max_rel_rms_err"] < 1e-5, case
    # `live` false left the state where it was, to the last digit
    assert (got["interleaved_decode"]["result"]["max_rel_rms_err"]
            == got["program"]["result"]["max_rel_rms_err"])
    for case in ("live_mask_off", "int8_weights", "residual_one",
                 "attention_by_sqrt", "two_groups", "no_decay",
                 "conv_not_carried"):
        result = got[case]["result"]
        assert result["ok"] is False and result["max_rel_rms_err"] > 1e-3, case
    # a bf16 state is told from a float32 one here, in float32 (on the chip,
    # under 40 bf16 layers, it is not: the configuration's `correctness.why`)
    assert got["state_bf16"]["result"]["ok"] is False
    assert 1e-5 < got["state_bf16"]["result"]["max_rel_rms_err"] < 1e-3
    for case in ("residual_one", "two_groups", "no_decay"):
        assert got[case]["result"]["max_rel_rms_err"] > 0.1, case


def test_the_precision_control_leaves_the_true_weights_behind(capsys,
                                                              monkeypatch):
    got = _checked("int8_weights,program", capsys, monkeypatch, seed="7")
    assert got["int8_weights"]["result"]["ok"] is False
    assert got["program"]["result"]["ok"] is True
    assert (got["program"]["result"]["max_rel_rms_err"] < 1e-5
            < got["int8_weights"]["result"]["max_rel_rms_err"])
