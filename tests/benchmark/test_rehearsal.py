"""The one command end to end on the CPU at `debug-tiny` width: launcher,
stock gateway, generator, collection and the final line. A rehearsal prints
`platform: cpu` and the harness's own check refuses it as a measurement
(exit code 4). Without `--rehearse` a run that finds no TPU exits non-zero
and prints no result."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import manifest as mf

HERE = os.path.dirname(os.path.abspath(__file__))
MANIFEST = os.path.join(HERE, "rehearsal", "BENCHMARK.json")
RUN = os.path.join(mf.ROOT, "benchmark", "run.py")


def rehearse(workload, trace, seed=5, seconds=2):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, RUN, "--manifest", MANIFEST, "--workload", workload,
         "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace),
         "--rehearse"],
        env=env, capture_output=True, text=True, timeout=280, cwd=mf.ROOT)
    return proc


@pytest.mark.parametrize("workload,trace", [
    ("tiny.open", 0), ("tiny.sessions", 1), ("tiny-moe.closed", 0),
    ("tiny-bias.closed", 1)])
def test_rehearsal_runs_end_to_end_and_is_refused_as_a_measurement(workload, trace):
    proc = rehearse(workload, trace, seed=2147483655)
    assert proc.returncode == 4, proc.stderr[-3000:]
    assert "not a measurement: platform cpu" in proc.stderr
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    rehearsal = mf.load(MANIFEST)
    wanted = {m["name"] for m in rehearsal["per_layer" if trace else "end_to_end"]}
    got = set(line["metrics"])
    if trace:
        # no device plane in a CPU trace: the device-trace readers find
        # nothing and are left out of the line, as the contract says
        assert {"sched.host_share", "sched.queue_wait_p50_s",
                "gateway.ttft_overhead_p50_s", "cache.prefix_hit_share",
                "client.ttft_p50_s", "client.ttft_p90_s",
                "client.send_lag_p99_s", "engine.compiles_in_window",
                # the host timeline and the set-up ledger of PR 24
                "sched.activate_share", "sched.loop_overhead_share",
                "sched.loop_unattributed_share", "sched.longest_stall_s",
                "setup.programs_built", "setup.trace_lower_s",
                "setup.backend_compile_s", "setup.prewarm_build_s",
                "engine.programs_built_in_window"} <= got <= wanted
        if workload == "tiny.sessions":
            assert line["metrics"]["cache.prefix_hit_share"]["value"] > 0
        assert "window_s" in line["device"]
    else:
        assert got == wanted
        assert all(v["value"] > 0 for v in line["metrics"].values())
    split = json.loads(proc.stdout.strip().splitlines()[-2])
    assert split["correctness"]["ok"] and split["setup_split"]["warmup_s"] > 0
    # a mixture's `correct` has heard the routing; a dense model's has not
    assert ("routing_agreement" in split["correctness"]) == (
        workload == "tiny-moe.closed")


def test_an_architecture_arrives_as_files():
    """`tiny-bias.closed` above ran a `model_type` that neither table of the
    harness held before PR 26, against a reference that lies beside the
    rehearsal's manifest: no file of `benchmark/` knows either name."""
    config = mf.load_config(mf.load(MANIFEST), "debug-bias-tiny", os.path.dirname(MANIFEST))
    reference = config["correctness"]["reference"]
    assert os.path.isfile(os.path.join(HERE, "rehearsal", "reference",
                                       reference + ".py"))
    for root, _dirs, files in os.walk(os.path.join(mf.ROOT, "benchmark")):
        assert reference + ".py" not in files
        for fn in files:
            if fn.endswith((".py", ".json")):
                with open(os.path.join(root, fn)) as f:
                    text = f.read()
                for name in (config["model_type"], reference,
                             config["model_id"]):
                    assert name not in text, f"{fn} knows {name!r}"


def test_without_rehearse_a_run_that_finds_no_tpu_prints_no_result():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, RUN, "--manifest", MANIFEST, "--workload", "tiny.open",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=120, cwd=mf.ROOT)
    assert proc.returncode not in (0, 4)
    assert proc.stdout.strip() == ""


def test_a_directory_with_only_the_benchmark_prints_no_result(tmp_path):
    shutil.copy(mf.MANIFEST_PATH, tmp_path / "BENCHMARK.json")
    for d in mf.load()["paths"]:
        shutil.copytree(os.path.join(mf.ROOT, d), tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "mistral-7b-l16.decode-saturated", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, timeout=60, cwd=tmp_path)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
