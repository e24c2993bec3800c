"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of BENCHMARK.json: builds the cell's configuration with random
weights from the seed in a child process pinned to the chip (launcher.py),
starts the stock gateway in a second child and registers the engine with it,
warms up every program the cell's traffic reaches, offers the cell's traffic
through the gateway for `--seconds`, and prints one JSON object as the last
line of stdout (end-to-end metrics with `--trace 0`, per-layer metrics and
the breakdown with `--trace 1`).

This process never imports jax: a chip belongs to one process. It is the load
generator — one process, one event loop.

`--rehearse` (with JAX_PLATFORMS=cpu) runs the same path on the CPU for tests
of the harness; the line it prints says platform cpu and the exit code is 4:
a rehearsal is not a measurement.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import shutil
import subprocess
import sys
import time

PROCESS_START = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark import manifest as mf  # noqa: E402
from benchmark import peaks as peaks_mod  # noqa: E402
from benchmark import warmup  # noqa: E402
from benchmark.procs import ONE_CHIP_ENV, Children, free_port, note  # noqa: E402
from benchmark.samples import request_ok  # noqa: E402
from benchmark.tokenizer import count_words  # noqa: E402

EXIT_NOT_A_MEASUREMENT = 4
EXIT_SWEEP = 5


class BenchFailure(Exception):
    pass


class Ctx:
    """What a generator drives: a clock whose zero is the start of the
    window, and `send`."""

    def __init__(self, session, base: str, headers: dict, model: str,
                 traffic: dict, seed: int, seconds: int, vocab: int,
                 timeout_s: float):
        self.session, self.base, self.headers = session, base, headers
        self.model, self.traffic, self.seed = model, traffic, seed
        self.seconds, self.vocab, self.timeout_s = seconds, vocab, timeout_s
        self.t0 = None  # monotonic instant of the window's start
        self.records: list[dict] = []
        self.window_tokens = 0
        self._n = 0

    def now(self) -> float:
        return time.monotonic() - self.t0

    async def sleep_until(self, t: float) -> None:
        delay = t - self.now()
        if delay > 0:
            await asyncio.sleep(delay)

    async def send(self, messages: list[dict], max_tokens: int, *,
                   due_s: float, prompt_tokens: int, in_sample: bool,
                   kind: str = "request", on_first=None,
                   on_words=None) -> dict:
        import aiohttp

        self._n += 1
        rid = f"bench-{self.seed}-{self._n}"
        rec = {"id": rid, "due_s": due_s, "send_s": self.now(),
               "prompt_tokens": prompt_tokens, "max_tokens": max_tokens,
               "in_sample": in_sample, "kind": kind, "first_s": None,
               "last_s": None, "end_s": None, "words": 0, "text": None,
               "completion_tokens": None, "usage_prompt_tokens": None,
               "status": None, "error": None, "finish_reason": None}
        self.records.append(rec)
        body = {"model": self.model, "temperature": 0, "stream": True,
                "max_tokens": max_tokens, "messages": messages,
                "stream_options": {"include_usage": True}}
        parts: list[str] = []
        try:
            async with self.session.post(
                    f"{self.base}/v1/chat/completions", json=body,
                    headers={**self.headers, "X-Request-Id": rid},
                    timeout=aiohttp.ClientTimeout(total=self.timeout_s)) as resp:
                rec["status"] = resp.status
                if resp.status != 200:
                    rec["error"] = (await resp.text())[:300]
                    return rec
                async for raw in resp.content:
                    if not raw.startswith(b"data:"):
                        continue
                    data = raw[5:].strip()
                    if data == b"[DONE]":
                        continue
                    frame = json.loads(data)
                    if "error" in frame:
                        rec["error"] = str(frame["error"])[:300]
                    text = ""
                    for choice in frame.get("choices") or ():
                        text += (choice.get("delta") or {}).get("content") or ""
                        if choice.get("finish_reason"):
                            rec["finish_reason"] = choice["finish_reason"]
                    if frame.get("usage"):
                        rec["completion_tokens"] = frame["usage"].get(
                            "completion_tokens")
                        rec["usage_prompt_tokens"] = frame["usage"].get(
                            "prompt_tokens")
                    if text:
                        t = self.now()
                        n = count_words(text)
                        if rec["first_s"] is None:
                            rec["first_s"] = t
                            if on_first is not None:
                                on_first()
                        rec["last_s"] = t
                        rec["words"] += n
                        if on_words is not None:
                            on_words(rec["words"])
                        if 0 <= t < self.seconds:
                            self.window_tokens += n
                        parts.append(text)
            rec["text"] = "".join(parts)
        except asyncio.CancelledError:
            rec["error"] = "cancelled by the generator"
            raise
        except Exception as e:  # connection errors count as failures
            rec["error"] = f"{type(e).__name__}: {e}"[:300]
        finally:
            rec["end_s"] = self.now()
        return rec


async def http_json(session, method: str, url: str, body=None, headers=None,
                    timeout: float = 60.0):
    import aiohttp

    async with session.request(
            method, url, json=body, headers=headers,
            timeout=aiohttp.ClientTimeout(total=timeout)) as r:
        text = await r.text()
        try:
            return r.status, json.loads(text or "null")
        except ValueError:
            return r.status, {"raw": text[:500]}


async def wait_http(session, url: str, proc, what: str, timeout_s: float):
    start = time.monotonic()
    while time.monotonic() - start < timeout_s:
        if proc.poll() is not None:
            raise BenchFailure(f"{what} exited with code {proc.returncode} "
                               "before it was ready")
        try:
            status, _ = await http_json(session, "GET", url, timeout=3)
            if status == 200:
                return time.monotonic() - start
        except Exception:
            pass
        await asyncio.sleep(0.25)
    raise BenchFailure(f"{what} not ready after {timeout_s:.0f}s")


async def register_gateway(session, gateway: str, engine: str,
                           password: str) -> dict:
    """Admin logged in, engine registered as tpu://, inference key minted
    (chip_smoke.start_gateway's recipe)."""
    status, login = await http_json(session, "POST", f"{gateway}/api/auth/login",
                                    {"username": "admin", "password": password})
    if status != 200:
        raise BenchFailure(f"gateway login failed: {status} {login}")
    admin = {"Authorization": f"Bearer {login['token']}"}
    status, ep = await http_json(session, "POST", f"{gateway}/api/endpoints",
                                 {"base_url": engine, "name": "bench-engine"},
                                 headers=admin)
    if status != 201 or ep.get("endpoint_type") != "tpu":
        raise BenchFailure(f"engine did not register as tpu://: {status} {ep}")
    status, key = await http_json(
        session, "POST", f"{gateway}/api/api-keys",
        {"name": "bench", "permissions": ["openai.inference"]}, headers=admin)
    if status not in (200, 201):
        raise BenchFailure(f"api key not minted: {status} {key}")
    return {"Authorization": f"Bearer {key['api_key']}"}


async def warm_up(ctx: Ctx, waves, blocker: dict, vocab: int, seed: int,
                  group_formed=None) -> list[dict]:
    """Send the warm-up waves one after another. A wave of several requests
    follows a short blocker request: once the blocker's first token is out
    the engine is inside a decode burst, the wave queues up behind it, and
    the next admission prefills it as ONE group. `group_formed(g, since)`
    says whether the engine did prefill a group that pads to g; a wave that
    arrived too spread out is sent again, a few times."""
    import random

    from benchmark.generators.common import single_message

    rng = random.Random(seed ^ 0xA11CE)
    timings = []
    ctx.t0 = time.monotonic()

    async def one_wave(wave):
        block_task = None
        if len(wave) > 1:
            started = asyncio.Event()
            block_task = asyncio.create_task(ctx.send(
                single_message(rng, blocker["prompt_tokens"], vocab),
                blocker["max_tokens"], due_s=ctx.now(),
                prompt_tokens=blocker["prompt_tokens"], in_sample=False,
                kind="warmup", on_first=started.set))
            waiter = asyncio.create_task(started.wait())
            await asyncio.wait([block_task, waiter],
                               return_when=asyncio.FIRST_COMPLETED)
            waiter.cancel()
        recs = await asyncio.gather(*[
            ctx.send(single_message(rng, p, vocab), m, due_s=ctx.now(),
                     prompt_tokens=p, in_sample=False, kind="warmup")
            for p, m in wave])
        if block_task is not None:
            recs.append(await block_task)
        bad = [r for r in recs if not request_ok(r)]
        if bad:
            raise BenchFailure(f"warm-up request failed: {bad[0]}")

    for wave in waves:
        start, wall = time.monotonic(), time.time()
        tries = 1
        await one_wave(wave)
        while (len(wave) > 1 and group_formed is not None and tries < 4
               and not await group_formed(len(wave), wall)):
            tries += 1
            await one_wave(wave)
        timings.append({"prompt_tokens": wave[0][0], "group": len(wave),
                        "tries": tries,
                        "s": round(time.monotonic() - start, 3)})
    return timings


async def engine_timelines(session, engine: str, recs: list[dict]) -> dict:
    """The engine's own account of each sampled request (flight recorder):
    its time to first token and how long it queued before its first prefill."""
    out = {}
    sem = asyncio.Semaphore(8)

    async def one(rec):
        async with sem:
            status, tl = await http_json(
                session, "GET", f"{engine}/api/requests/{rec['id']}/timeline")
        if status != 200:
            return
        admitted = first_chunk = ttft = None
        cached = 0
        for e in tl.get("events") or ():
            attrs = e.get("attrs") or {}
            if e["event"] == "admitted" and admitted is None:
                admitted = e["ts"]
            elif e["event"] == "prefill_chunk":
                if first_chunk is None:
                    first_chunk = e["ts"]
                cached = max(cached, attrs.get("cached_tokens", 0))
            elif e["event"] == "finished":
                ttft = attrs.get("ttft_s")
        out[rec["id"]] = {
            "ttft_s": ttft, "cached_tokens": cached,
            "queue_wait_s": (first_chunk - admitted
                             if admitted is not None and first_chunk is not None
                             else None)}

    await asyncio.gather(*[one(r) for r in recs])
    return out


async def sweep_rates(ctx_factory, gen, traffic: dict, rates: list[float],
                      seconds: int) -> list[dict]:
    """The knee sweep of an open-loop cell: one engine, one warm-up, then
    each rate for `seconds` after a 5 s ramp, the backlog drained between
    rates. For each: the requests in flight at the window's middle and end
    (no backlog grows while the end is no higher than the middle; band
    averages over the 5 s before each instant beside the instants), and
    the latencies at that rate."""
    from benchmark import samples, stats

    rows = []
    for rate in rates:
        ctx = ctx_factory({**traffic, "rate_per_s": rate, "ramp_s": 5,
                           "tail_s": 0})
        ctx.t0 = time.monotonic() + 5
        plan = gen.schedule(ctx.traffic, ctx.seed, seconds, ctx.vocab)
        tasks = []
        counts: list[tuple[float, int]] = []

        async def sender():
            for r in plan:
                await ctx.sleep_until(r["due_s"])
                tasks.append(asyncio.create_task(ctx.send(
                    r["messages"], r["max_tokens"], due_s=r["due_s"],
                    prompt_tokens=r["prompt_tokens"], in_sample=r["in_sample"])))

        async def watcher():
            while ctx.now() < seconds:
                counts.append((ctx.now(), sum(1 for t in tasks if not t.done())))
                await asyncio.sleep(0.25)

        await asyncio.gather(sender(), watcher())
        end_count = sum(1 for t in tasks if not t.done())
        t_drain = time.monotonic()
        await asyncio.gather(*tasks)
        band = lambda lo, hi: stats.percentile(  # noqa: E731
            [c for t, c in counts if lo <= t < hi], 50)
        mid_count = min(counts, key=lambda tc: abs(tc[0] - seconds / 2))[1]
        c = {"sample": [r for r in ctx.records if r["in_sample"]]}
        rows.append({
            "rate_per_s": rate, "requests": len(c["sample"]),
            "failed": sum(1 for r in c["sample"] if not request_ok(r)),
            "in_flight_mid": mid_count, "in_flight_end": end_count,
            "in_flight_mid_band": band(seconds / 2 - 5, seconds / 2),
            "in_flight_end_band": band(seconds - 5, seconds),
            "backlog_grows": end_count > mid_count,
            "drain_s": time.monotonic() - t_drain,
            "ttft_p50_s": stats.percentile(samples.ttfts(c), 50),
            "ttft_p90_s": stats.percentile(samples.ttfts(c), 90),
            "tpot_p50_s": stats.percentile(samples.tpots(c), 50),
        })
        note(f"sweep: {json.dumps(rows[-1])}")
    return rows


async def run_cell(args, cell: dict, config: dict, config_path: str,
                   traffic: dict, settings: dict, children: Children,
                   run_dir: str) -> tuple[dict, dict]:
    import aiohttp

    split: dict = {}
    platform = "tpu"
    if args.rehearse:
        platform = os.environ.get("JAX_PLATFORMS", "").split(",")[0] or "cpu"
    base_env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    engine_env = {**base_env,
                  "LLMLB_DRAIN_GRACE_S": "0",
                  "LLMLB_FLIGHTREC_REQS": str(settings["flightrec_requests"])}
    if cell["chips"] == 1 and platform == "tpu":
        engine_env.update(ONE_CHIP_ENV)
    if args.rehearse:
        # tests/conftest.py gives the test process 8 virtual devices; the
        # rehearsed engine is a one-device engine
        engine_env.pop("XLA_FLAGS", None)
    engine_port, gateway_port = free_port(), free_port()
    engine = f"http://127.0.0.1:{engine_port}"
    gateway = f"http://127.0.0.1:{gateway_port}"
    launcher_argv = [sys.executable, os.path.join(HERE, "launcher.py"),
                     "--config", config_path, "--seed", str(args.seed),
                     "--base", os.path.dirname(os.path.abspath(args.manifest)),
                     "--port", str(engine_port), "--chips", str(cell["chips"]),
                     "--platform", platform,
                     "--trace-dir", os.path.join(run_dir, "trace")]
    if args.dump_trace_structure:
        launcher_argv += ["--dump-trace-structure", args.dump_trace_structure]
    t_children = time.monotonic()
    engine_proc = children.start("engine", launcher_argv, engine_env)
    data_dir = os.path.join(run_dir, "gateway")
    shutil.rmtree(data_dir, ignore_errors=True)
    os.makedirs(data_dir)
    password = "bench-admin-1"
    gateway_proc = children.start(
        "gateway",
        [sys.executable, "-m", "llmlb_tpu.gateway.server", "serve",
         "--host", "127.0.0.1", "--port", str(gateway_port)],
        {**base_env, "LLMLB_DATA_DIR": data_dir,
         "LLMLB_LOG_DIR": os.path.join(data_dir, "logs"),
         "LLMLB_ADMIN_PASSWORD": password})

    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(connector=conn) as session:
        split["gateway_ready_s"] = await wait_http(
            session, f"{gateway}/health", gateway_proc, "gateway",
            settings["gateway_ready_timeout_s"])
        await wait_http(session, f"{engine}/api/health", engine_proc, "engine",
                        settings["engine_ready_timeout_s"])
        split["engine_ready_s"] = time.monotonic() - t_children
        _, info = await http_json(session, "GET", f"{engine}/bench/info")
        split.update(info["setup_split"])
        device = info["device"]
        if device["platform"] != platform or device["count"] != cell["chips"]:
            raise BenchFailure(f"the engine runs on {device}, the cell wants "
                               f"{cell['chips']} x {platform}")
        t = time.monotonic()
        key = await register_gateway(session, gateway, engine, password)
        split["gateway_register_s"] = time.monotonic() - t

        gen = mf.load_module("generators", traffic["generator"])
        vocab = config["vocab_size"]
        ctx = Ctx(session, gateway, key, config["model_id"], traffic, args.seed,
                  args.seconds, vocab, settings["request_timeout_s"])
        t = time.monotonic()
        waves = warmup.plan(gen.shapes(traffic), info["engine"])

        async def group_formed(g: int, since_wall: float) -> bool:
            _, st = await http_json(session, "GET", f"{engine}/bench/steps")
            return any(r["kind"] == "prefill" and r["ts"] >= since_wall
                       and g // 2 < r["active_slots"] <= g
                       for r in st["records"])

        split["warmup_waves"] = await warm_up(
            ctx, waves, settings["warmup_blocker"], vocab, args.seed,
            group_formed)
        split["warmup_s"] = time.monotonic() - t
        _, after_warm = await http_json(session, "GET", f"{engine}/bench/info")
        split["programs_after_warmup"] = after_warm["compiles"]
        _, warm_steps = await http_json(session, "GET", f"{engine}/bench/steps")
        groups: dict[str, int] = {}
        for r in warm_steps["records"]:
            if r["kind"] == "prefill":
                k = str(r["active_slots"])
                groups[k] = groups.get(k, 0) + 1
        split["warmup_prefill_groups"] = groups  # group size -> dispatches
        note(f"warm-up: {len(waves)} waves in {split['warmup_s']:.1f}s, "
             f"programs {after_warm['compiles']}")

        if args.sweep:
            rows = await sweep_rates(
                lambda t: Ctx(session, gateway, key, config["model_id"], t,
                              args.seed, args.seconds, vocab,
                              settings["request_timeout_s"]),
                gen, traffic, [float(x) for x in args.sweep.split(",")],
                args.seconds)
            return {"sweep": rows, "device": info["device"]}, split

        # ---- the ramp (set-up) and the window
        ramp_s = float(traffic.get("ramp_s", 0))
        ctx.records = []
        ctx.t0 = time.monotonic() + ramp_s
        setup_s = ctx.t0 - PROCESS_START
        split["ramp_s"] = ramp_s
        drive = asyncio.create_task(gen.drive(ctx))
        trace_task = None
        trace_s = min(float(settings["trace_s"]), float(args.seconds))

        async def at_window_start():
            await ctx.sleep_until(0.0)
            _, m0 = await http_json(session, "POST", f"{engine}/bench/mark",
                                    {"name": "window_start", "reset_steps": True})
            _, h0 = await http_json(session, "GET", f"{engine}/api/health")
            return m0, h0

        async def traced():
            await ctx.sleep_until(args.seconds - trace_s)
            await http_json(session, "POST", f"{engine}/bench/trace/start")
            await ctx.sleep_until(args.seconds)
            _, out = await http_json(session, "POST",
                                     f"{engine}/bench/trace/stop", timeout=240)
            return out

        start_task = asyncio.create_task(at_window_start())
        if args.trace:
            trace_task = asyncio.create_task(traced())
        await ctx.sleep_until(args.seconds)
        _, m1 = await http_json(session, "POST", f"{engine}/bench/mark",
                                {"name": "window_end"})
        _, h1 = await http_json(session, "GET", f"{engine}/api/health")
        _, steps = await http_json(session, "GET", f"{engine}/bench/steps")
        m0, h0 = await start_task
        await drive  # the generator returns once its sample has finished
        trace_out = await trace_task if trace_task else None
        sample = [r for r in ctx.records if r["in_sample"]]
        timelines = {}
        if args.trace:
            timelines = await engine_timelines(session, engine, sample)
        _, final = await http_json(session, "GET", f"{engine}/bench/info")

    collected = {
        "cell": cell, "config": config, "traffic": traffic,
        "settings": settings, "seconds": args.seconds, "trace_run": args.trace,
        "requests": ctx.records, "sample": sample,
        "window_tokens": ctx.window_tokens, "setup_s": setup_s,
        "engine": info["engine"], "correctness": info["correctness"],
        "marks": {"window_start": m0, "window_end": m1},
        "health_start": h0, "health_end": h1,
        "steps": [r for r in steps["records"]
                  if m0["wall"] <= r["ts"] <= m1["wall"]],
        "timelines": timelines, "trace": trace_out,
        "device": {**final["device"]}, "setup_split": split,
        "compiles_in_window": (m1["compiles"]["programs"]
                               - m0["compiles"]["programs"]),
        "compiled_in_window": final["compile_names"][
            m0["compiles"]["programs"]:m1["compiles"]["programs"]],
    }
    try:
        collected["peaks"] = peaks_mod.peaks_for(device["kind"])
    except peaks_mod.UnknownDevice:
        if not args.rehearse:
            raise
        collected["peaks"] = None
    return collected, split


def result_line(manifest: dict, cell: dict, collected: dict, trace: bool) -> dict:
    section = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in mf.metrics_for(manifest, section, cell["name"]):
        if m["name"] == "setup_s":
            value = collected["setup_s"]
        else:
            kind = "layer_metrics" if trace else "e2e_metrics"
            value = mf.load_module(kind, m["name"]).read(collected)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    sample = collected["sample"]
    failed = sum(1 for r in sample if not request_ok(r))
    exact = all(r["completion_tokens"] == r["max_tokens"]
                and r["words"] == r["max_tokens"]
                for r in sample if r["status"] == 200 and not r["error"])
    device = dict(collected["device"])
    out = {"correct": bool(collected["correctness"]["ok"] and exact
                           and len(sample) > 0),
           "attempted": len(sample), "failed": failed, "metrics": metrics,
           "device": device}
    if trace and collected.get("trace"):
        tr = collected["trace"]
        device["busy_s"] = tr.get("busy_s")
        device["window_s"] = tr.get("window_s")
        if tr.get("breakdown"):
            out["breakdown"] = tr["breakdown"]
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=int, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=mf.MANIFEST_PATH,
                    help="another manifest than BENCHMARK.json (rehearsals)")
    ap.add_argument("--rehearse", action="store_true",
                    help="run on the platform JAX_PLATFORMS names; the "
                         "result is not a measurement (exit code 4)")
    ap.add_argument("--dump-trace-structure", default=None)
    ap.add_argument("--sweep", default=None,
                    help="comma-separated rates: sweep an open-loop cell for "
                         "its knee instead of measuring it (exit code 5)")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(ROOT, "llmlb_tpu")):
        print("benchmark/run.py needs a checkout of the repository: "
              "llmlb_tpu/ is not beside benchmark/", file=sys.stderr)
        return 2
    manifest = mf.load(args.manifest)
    problems = mf.check(manifest) if args.manifest == mf.MANIFEST_PATH else []
    if problems:
        print("BENCHMARK.json: " + "; ".join(problems), file=sys.stderr)
        return 2
    cell = mf.cell(manifest, args.workload)
    if args.seconds is None:
        args.seconds = manifest["run_seconds"]
    base = os.path.dirname(os.path.abspath(args.manifest))
    config_path = mf.config_path(manifest, cell["config"], base)
    config = mf.load_config(manifest, cell["config"], base)
    traffic = mf.load_traffic(cell["traffic"], base)
    with open(os.path.join(HERE, "settings.json")) as f:
        settings = json.load(f)

    run_dir = os.path.join(ROOT, ".bench_run", cell["name"])
    os.makedirs(run_dir, exist_ok=True)
    # both servers run `make -C native` at start; build once, here, so that
    # they do not race
    subprocess.run(["make", "-C", os.path.join(ROOT, "native")],
                   capture_output=True)
    children = Children(run_dir, ROOT)
    try:
        collected, split = asyncio.run(run_cell(
            args, cell, config, config_path, traffic, settings, children,
            run_dir))
    except (BenchFailure, KeyboardInterrupt) as e:
        note(f"FAILED: {e or type(e).__name__}")
        children.log_tails()
        return 1
    finally:
        children.stop()
    if "jax" in sys.modules:  # a parent that touched jax would hold the chip
        raise RuntimeError("the load generator imported jax")
    if args.sweep:
        print(json.dumps(collected), flush=True)
        return EXIT_SWEEP
    line = result_line(manifest, cell, collected, bool(args.trace))
    with open(os.path.join(run_dir, "last_run.json"), "w") as f:
        json.dump({"args": vars(args), "line": line, "setup_split": split,
                   "correctness": collected["correctness"],
                   "compiles_in_window": collected["compiles_in_window"],
                   "steps": collected["steps"],  # to name a stall's phase
                   "requests": [{k: v for k, v in r.items() if k != "text"}
                                for r in collected["requests"]]}, f)
    print(json.dumps({"setup_split": split,
                      "correctness": collected["correctness"],
                      "compiles_in_window": collected["compiles_in_window"],
                      "compiled_in_window": collected["compiled_in_window"],
                      "requests_sent": len(collected["requests"])}))
    print(json.dumps(line), flush=True)
    if args.rehearse or line["device"]["platform"] != "tpu":
        print(f"not a measurement: platform {line['device']['platform']}",
              file=sys.stderr)
        return EXIT_NOT_A_MEASUREMENT
    return 0


if __name__ == "__main__":
    sys.exit(main())
