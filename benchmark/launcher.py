"""The engine's process: the system under test, built from a configuration
file and `--seed`, served over HTTP exactly as `python -m
llmlb_tpu.engine.server` serves it (`EngineCore` → `Engine` →
`create_engine_app`), which cannot be used as it is because its `--preset`
knows neither configuration's depth.

Beyond the program's own routes, this process answers a few of the
benchmark's under `/bench/` — only the process that holds the chip can read
its memory, hear its compiles or trace it:

  GET  /bench/info          device, set-up split, correctness, counters
  POST /bench/mark          {"name": ...} snapshot of counters at an instant
  POST /bench/trace/start   start jax.profiler on the device
  POST /bench/trace/stop    stop, reduce the trace (benchmark/trace.py)
  GET  /bench/steps         every stepstats record since the last start mark

Run by benchmark/run.py; one chip, one process.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import shutil
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

T_START = time.monotonic()


def note(msg: str) -> None:
    print(f"[launcher +{time.monotonic() - T_START:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


class CompileCounter:
    """Programs built in this process, heard through `jax.monitoring`: every
    new program — compiled, or fetched from the persistent cache — ends in
    one backend-compile duration event. The engine counts none of its own."""

    def __init__(self):
        self.programs = 0  # compiled or fetched: a new shape either way
        self.cache_hits = 0
        self.seconds = 0.0
        self.names: list[str] = []  # in the order built
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += seconds
            self.names.append(str(kw.get("fun_name")))

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"programs": self.programs, "cache_hits": self.cache_hits,
                "compiled": self.programs - self.cache_hits,
                "seconds": self.seconds}


class StepCollector(threading.Thread):
    """The stepstats ring holds 512 records; a window makes more. Copy what
    is new every second, by sequence number."""

    def __init__(self, recorder):
        super().__init__(name="bench-steps", daemon=True)
        self.recorder = recorder
        self.records: list[dict] = []
        self._seen = 0
        self._lock = threading.Lock()
        self._stop = threading.Event()

    def reset(self) -> None:
        with self._lock:
            self.pull()
            self.records = []

    def pull(self) -> None:
        snap = self.recorder.snapshot(limit=self.recorder.capacity)
        new = [r for r in snap["records"] if r["seq"] > self._seen]
        if new:
            new.sort(key=lambda r: r["seq"])
            self._seen = new[-1]["seq"]
            self.records.extend(new)

    def run(self) -> None:
        while not self._stop.wait(1.0):
            with self._lock:
                self.pull()

    def all(self) -> list[dict]:
        with self._lock:
            self.pull()
            return list(self.records)


def build_cfg(config: dict):
    """The program's configuration object for a published `config.json`,
    asked of the program as a deployment asks it: `load_config` reads the
    model directory's `config.json` and picks the class. The harness names
    no architecture; a new one is the program's to know."""
    import tempfile

    import jax.numpy as jnp

    from llmlb_tpu.engine.weights import load_config

    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        config.get("torch_dtype", "bfloat16")]
    with tempfile.TemporaryDirectory(prefix="bench-config-") as model_dir:
        with open(os.path.join(model_dir, "config.json"), "w") as f:
            json.dump(config, f)
        return load_config(model_dir, dtype)


def mesh_config_for(cfg, n_devices: int):
    """The mesh `EngineCore` would choose by default for this many devices
    (scheduler.py: experts first, then tensor parallelism, data parallelism
    with the rest) — named here so that the weights can be made already laid
    out on it."""
    import math

    from llmlb_tpu.parallel.mesh import MeshConfig, default_tp

    ep = 1
    if getattr(cfg, "num_experts", 0) > 1:
        ep = math.gcd(n_devices, cfg.num_experts)
    tp = default_tp(n_devices // ep, cfg.num_heads, cfg.num_kv_heads)
    return MeshConfig(dp=n_devices // (ep * tp), ep=ep, tp=tp)


def make_params(family, cfg, seed: int, mesh):
    """Random weights from the seed, made on the device in ONE jitted call,
    in the type and the layout they are served in (so that `EngineCore`'s
    own `device_put` finds them in place and copies nothing)."""
    import jax

    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    def init(k):
        return family.init_params(cfg, k)

    layout = family.param_shardings(cfg, mesh)  # names quant/LoRA leaves too
    params = jax.jit(init, out_shardings={
        name: layout[name] for name in jax.eval_shape(init, key)})(key)
    jax.block_until_ready(params)
    return params


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--base", default=ROOT,
                    help="the directory of the manifest that lists --config")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--platform", default="tpu")
    ap.add_argument("--trace-dir", default=os.path.join(ROOT, ".bench_trace"))
    ap.add_argument("--dump-trace-structure", default=None)
    args = ap.parse_args()
    with open(args.config) as f:
        config = json.load(f)
    split: dict[str, float] = {}

    from llmlb_tpu.startup import configure_compile_cache, resolve_backend

    cache_dir = configure_compile_cache()
    import jax

    # every program goes to the cache, the sub-second ones too: a run pays
    # for each of them again otherwise
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    t = time.monotonic()
    devices = resolve_backend()
    split["backend_start_s"] = time.monotonic() - t
    if devices[0].platform != args.platform or len(devices) != args.chips:
        print(f"launcher: wanted {args.chips} x {args.platform}, JAX reports "
              f"{len(devices)} x {devices[0].platform}", file=sys.stderr)
        return 3
    compiles = CompileCounter()
    from llmlb_tpu.native import ensure_native_built

    ensure_native_built()
    from llmlb_tpu.engine.scheduler import EngineCore
    from llmlb_tpu.engine.server import create_engine_app
    from llmlb_tpu.engine.service import Engine
    from llmlb_tpu.models import family_for

    from benchmark import correctness, reference, trace as trace_mod
    from benchmark.tokenizer import WordTokenizer

    cfg = build_cfg(config)
    family = family_for(cfg)
    eng = config["engine"]
    t = time.monotonic()
    from llmlb_tpu.parallel.mesh import build_mesh

    mesh_config = mesh_config_for(cfg, len(devices))
    params = make_params(family, cfg, args.seed,
                         build_mesh(mesh_config, devices=devices))
    split["weights_s"] = time.monotonic() - t
    note(f"weights on device after {split['weights_s']:.1f}s")

    t = time.monotonic()
    correct = correctness.check(family, cfg, params, config,
                                config["correctness"], args.seed,
                                int(eng.get("kv_page_size", 128)),
                                reference.module_for(config, args.base))
    split["correctness_s"] = time.monotonic() - t
    note(f"correctness: {json.dumps(correct)}")

    t = time.monotonic()
    core = EngineCore(
        cfg, params, eos_id=-1, seed=args.seed & 0x7FFFFFFF,
        mesh_config=mesh_config,
        num_slots=int(eng["num_slots"]),
        slot_capacity=int(eng["slot_capacity"]),
        prefill_buckets=tuple(eng["prefill_buckets"]),
        kv_layout=eng.get("kv_layout", "paged"),
        kv_page_size=int(eng.get("kv_page_size", 128)),
        kv_pages=eng.get("kv_pages"),
        decode_burst=eng.get("decode_burst"),
        prefix_cache=eng.get("prefix_cache", True),
    )
    del params
    core.start()
    engine = Engine(config["model_id"], core, WordTokenizer(cfg.vocab_size))
    split["engine_build_s"] = time.monotonic() - t
    steps = StepCollector(core.step_stats)
    steps.start()

    from aiohttp import web

    app = create_engine_app(engine)
    marks: dict[str, dict] = {}
    tracing: dict = {}
    dev = jax.local_devices()[0]

    def memory_peak() -> int:
        peaks = []
        for d in jax.local_devices():
            try:
                peaks.append(int((d.memory_stats() or {}).get(
                    "peak_bytes_in_use", 0)))
            except Exception:
                peaks.append(0)
        return max(peaks)

    async def info(_request):
        return web.json_response({
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices()),
                       "memory_peak_bytes": memory_peak()},
            "setup_split": split, "correctness": correct,
            "compiles": compiles.snapshot(), "compile_cache_dir": cache_dir,
            "compile_names": list(compiles.names),
            "marks": marks,
            "engine": {"num_slots": core.num_slots,
                       "slot_capacity": core.slot_capacity,
                       "prefill_buckets": list(core.prefill_buckets),
                       "window_buckets": list(core._window_buckets),
                       "kv_pages": core.kv_num_pages,
                       "kv_page_size": core.kv_page_size,
                       "decode_burst": core.decode_burst,
                       "param_bytes": core.param_bytes,
                       "n_params": core.n_params},
        })

    async def mark(request):
        body = await request.json()
        if body.get("reset_steps"):
            steps.reset()
        marks[body["name"]] = {"wall": time.time(),
                               "compiles": compiles.snapshot(),
                               "memory_peak_bytes": memory_peak()}
        return web.json_response(marks[body["name"]])

    async def get_steps(_request):
        return web.json_response({"records": steps.all()})

    def _start_trace():
        shutil.rmtree(args.trace_dir, ignore_errors=True)
        os.makedirs(args.trace_dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(args.trace_dir, profiler_options=opts)
        tracing["wall_start"] = time.time()
        tracing["mono_start"] = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.clock_sync"):
            tracing["sync_wall"] = time.time()
            time.sleep(0.001)

    def _stop_trace() -> dict:
        window_s = time.monotonic() - tracing["mono_start"]
        wall_stop = time.time()
        jax.profiler.stop_trace()
        found = []
        for root, _dirs, files in os.walk(args.trace_dir):
            found += [os.path.join(root, f) for f in files
                      if f.endswith(".xplane.pb")]
        if not found:
            return {"error": "the profiler wrote no .xplane.pb"}
        profile = jax.profiler.ProfileData.from_file(sorted(found)[-1])
        if args.dump_trace_structure:
            os.makedirs(os.path.dirname(args.dump_trace_structure),
                        exist_ok=True)
            with open(args.dump_trace_structure, "w") as f:
                json.dump(trace_mod.structure(profile), f, indent=1)
        sync = trace_mod.find_host_event(profile, "bench.clock_sync")
        offset = None if sync is None else tracing["sync_wall"] - sync
        recs = [r for r in steps.all()
                if tracing["wall_start"] - 1 <= r["ts"] <= wall_stop + 1]
        out = trace_mod.reduce(profile, window_s=window_s, steps=recs,
                               clock_offset_s=offset)
        out["wall_start"], out["wall_stop"] = tracing["wall_start"], wall_stop
        out["clock_offset_found"] = offset is not None
        shutil.rmtree(args.trace_dir, ignore_errors=True)
        return out

    async def trace_start(_request):
        await asyncio.get_running_loop().run_in_executor(None, _start_trace)
        return web.json_response({"started": tracing["wall_start"]})

    async def trace_stop(_request):
        out = await asyncio.get_running_loop().run_in_executor(
            None, _stop_trace)
        return web.json_response(out)

    app.router.add_get("/bench/info", info)
    app.router.add_post("/bench/mark", mark)
    app.router.add_get("/bench/steps", get_steps)
    app.router.add_post("/bench/trace/start", trace_start)
    app.router.add_post("/bench/trace/stop", trace_stop)
    note(f"serving {config['model_id']} on {len(devices)} x "
         f"{devices[0].device_kind}, port {args.port}, cache {cache_dir}")
    web.run_app(app, host="127.0.0.1", port=args.port, print=None)
    return 0


if __name__ == "__main__":
    sys.exit(main())
