"""Gateway overhead bench: req/s + latency through the full proxy path.

The reference's one committed benchmark is wrk against its Rust router with a
local upstream — 170,600 req/s, p50 0.249 ms (BASELINE.md). This measures the
same thing for this gateway: an in-process mock OpenAI upstream, the real app
(auth, audit, gate, TPS accounting all active), and N concurrent non-streaming
/v1/chat/completions callers. Run:

    python scripts/bench_gateway.py [--seconds 10] [--concurrency 50]

Prints one JSON line. Python/aiohttp will not reach a Rust router's ceiling;
the number bounds how much gateway CPU one TPU engine's request rate can
consume.

The gateway's own /metrics is scraped before and after the timed window and
the TTFT/E2E/queue-wait percentile deltas are printed under "prometheus", so
bench output and the Prometheus view agree on one source of truth.

Multi-worker modes (docs/deployment.md):

    python scripts/bench_gateway.py --workload throughput [--workers 4]

spawns REAL gateway processes (`serve --workers N`, SO_REUSEPORT) in front
of stub-engine processes and drives closed-loop load from separate client
processes, recording the 1..N scaling curve with p50/p99 at matched load
AND per-request gateway CPU from /proc (the core-count-independent figure
— see the docstring on run_throughput_bench for why wall-clock scaling on
a 2-core CI box measures the container, not the gateway).

    python scripts/bench_gateway.py --workload chaos --workers 4

runs the chaos drill across N shared-nothing worker states wired by the
real gossip bus: >=99% client success while an endpoint flaps, plus the
directly measured cross-worker breaker-propagation latency.

A second mode measures the prefix KV cache end to end with a REAL in-process
tpu:// engine (CPU backend) behind the gateway:

    python scripts/bench_gateway.py --workload shared-prefix [--requests 24]

Every request shares one long system prompt with a varying user tail — the
production chat shape. The bench classifies each request hit/miss from the
engine's own prefix counters and reports the hit rate, prefill tokens served
from cache, and mean TTFT split by hit vs miss, alongside the engine
/metrics exposition names so Prometheus shows the same story.

Disaggregated prefill/decode (docs/disaggregation.md):

    python scripts/bench_gateway.py --workload disagg

serves the slo-mix ITL scenario (background decoders + concurrent
420-token prompts) three ways — no protection, PR 10's chunk budget, and
PR 11's `--role split` — and reports background ITL, long-prompt TTFT,
the per-loop prefill-dispatch ledger (the zero-prefill-on-decode-loop
invariant), and handoff counts.

KV page shipping + host-RAM offload (docs/kv-cache.md):

    python scripts/bench_gateway.py --workload kv-ship

runs a 384-token-context preempt/resume and an evicted-prefix warm
return, each twice on identical traffic — replay (recompute) vs ship
(host-tier restore) — and reports resume gap, return TTFT, the
prefill-dispatch ledger (zero dispatches per shipped resume), and
cross-mode token identity.

Fused decode dispatch (docs/fused-decode.md):

    python scripts/bench_gateway.py --workload fused

drives mixed traffic (plain + LoRA + JSON-constrained, speculation and
int8 KV on) through the full gateway twice — LLMLB_FUSED_DECODE on vs
off — and reports per-step device dispatch counts from the scheduler's
ledger (fused holds exactly 1), decode tok/s both modes, and cross-mode
token identity.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import re
import sys
import time


def _pin_platform() -> None:
    """Every engine this harness builds is `debug-tiny` on the CPU, and its
    engine children are spawned with JAX_PLATFORMS=cpu: pin this process
    the same way before its first device touch."""
    os.environ["JAX_PLATFORMS"] = "cpu"

_SAMPLE_RE = re.compile(
    r"^([A-Za-z_:][A-Za-z0-9_:]*)\{(.*)\}\s+(-?[0-9.eE+]+)$"
)
_LE_RE = re.compile(r'le="([^"]+)"')

GATEWAY_HISTOGRAMS = (
    "llmlb_gateway_ttft_seconds",
    "llmlb_gateway_e2e_seconds",
    "llmlb_gateway_queue_wait_seconds",
)


def parse_gateway_histograms(text: str) -> dict:
    """Cumulative bucket counts per histogram family, summed across label
    sets (models/endpoints): {family: {le: count}}."""
    out: dict[str, dict[str, float]] = {name: {} for name in GATEWAY_HISTOGRAMS}
    for line in text.splitlines():
        m = _SAMPLE_RE.match(line)
        if not m:
            continue
        name, labels, value = m.group(1), m.group(2), float(m.group(3))
        for family in GATEWAY_HISTOGRAMS:
            if name == family + "_bucket":
                le = _LE_RE.search(labels)
                if le:
                    buckets = out[family]
                    buckets[le.group(1)] = buckets.get(le.group(1), 0.0) + value
    return out


def delta_percentile(before: dict, after: dict, pct: float) -> float | None:
    """Percentile of the requests observed BETWEEN two scrapes, linearly
    interpolated within the landing bucket — the same estimate Prometheus'
    histogram_quantile makes over a rate() window."""
    edges = sorted((k for k in after if k != "+Inf"), key=float)
    deltas = []
    for le in edges + ["+Inf"]:
        deltas.append(after.get(le, 0.0) - before.get(le, 0.0))
    total = deltas[-1]
    if total <= 0:
        return None
    target = total * pct / 100.0
    lower = 0.0
    prev_cum = 0.0
    for le, cum in zip(edges, deltas[:-1]):
        count = cum - prev_cum
        if count > 0 and cum >= target:
            frac = (target - prev_cum) / count
            return lower + frac * (float(le) - lower)
        prev_cum = cum
        lower = float(le)
    return float(edges[-1]) if edges else None


async def scrape_metrics(gw) -> dict:
    """One GET /metrics, parsed into per-family cumulative buckets."""
    resp = await gw.client.get("/metrics")
    assert resp.status == 200, await resp.text()
    return parse_gateway_histograms(await resp.text())


async def run_bench(seconds: float, concurrency: int) -> dict:
    from tests.support import GatewayHarness, MockOpenAIEndpoint

    gw = await GatewayHarness.create()
    upstream = await MockOpenAIEndpoint(model="bench-model").start()
    try:
        gw.register_mock(upstream.url, ["bench-model"])
        headers = dict(await gw.inference_headers())
        payload = {
            "model": "bench-model",
            "messages": [{"role": "user", "content": "ping"}],
            "stream": False,
        }

        # warmup
        for _ in range(20):
            resp = await gw.client.post(
                "/v1/chat/completions", json=payload, headers=headers
            )
            assert resp.status == 200, await resp.text()
            await resp.read()

        # Scrape-before: the percentile deltas below cover exactly the timed
        # window, so bench output and Prometheus agree on one source of truth.
        before = await scrape_metrics(gw)

        latencies: list[float] = []
        done = 0
        errors = 0
        deadline = time.perf_counter() + seconds

        async def worker() -> None:
            nonlocal done, errors
            while time.perf_counter() < deadline:
                t0 = time.perf_counter()
                try:
                    resp = await gw.client.post(
                        "/v1/chat/completions", json=payload, headers=headers
                    )
                    await resp.read()
                    if resp.status == 200:
                        done += 1
                        latencies.append(time.perf_counter() - t0)
                    else:
                        errors += 1
                except Exception:
                    errors += 1

        t0 = time.perf_counter()
        await asyncio.gather(*(worker() for _ in range(concurrency)))
        elapsed = time.perf_counter() - t0

        after = await scrape_metrics(gw)
        prom = {}
        for family, short in (("llmlb_gateway_ttft_seconds", "ttft"),
                              ("llmlb_gateway_e2e_seconds", "e2e"),
                              ("llmlb_gateway_queue_wait_seconds",
                               "queue_wait")):
            for p in (50, 99):
                v = delta_percentile(before[family], after[family], p)
                prom[f"{short}_p{p}_ms"] = (round(v * 1000, 3)
                                            if v is not None else None)

        # SLO goodput: the same attainment counters Prometheus scrapes
        # (llmlb_gateway_slo_*), summarized as the bench's goodput line
        resp = await gw.client.get("/metrics")
        exposition = await resp.text()

        def slo_sum(name: str) -> float:
            total = 0.0
            for line in exposition.splitlines():
                if line.startswith(name + "{") or line.startswith(name + " "):
                    total += float(line.rsplit(" ", 1)[1])
            return total

        eligible = slo_sum("llmlb_gateway_slo_eligible_total")
        met = slo_sum("llmlb_gateway_slo_met_total")
        slo_cfg = gw.state.metrics.slo
        goodput = {
            "slo_eligible": int(eligible),
            "slo_met": int(met),
            "ratio": round(met / eligible, 4) if eligible else None,
            "ttft_miss": int(slo_sum("llmlb_gateway_slo_ttft_miss_total")),
            "itl_miss": int(slo_sum("llmlb_gateway_slo_itl_miss_total")),
            "ttft_target_ms": (round(slo_cfg.ttft_target_s * 1000, 1)
                               if slo_cfg else None),
            "itl_target_ms": (round(slo_cfg.itl_target_s * 1000, 1)
                              if slo_cfg else None),
        }
        print(
            f"[bench] goodput: {goodput['slo_met']}/{goodput['slo_eligible']}"
            f" requests met SLO (ratio {goodput['ratio']}, TTFT target "
            f"{goodput['ttft_target_ms']}ms, ITL target "
            f"{goodput['itl_target_ms']}ms)",
            file=sys.stderr,
        )

        latencies.sort()

        def pct(p: float) -> float:
            if not latencies:
                return 0.0
            return latencies[min(len(latencies) - 1, int(len(latencies) * p))]

        return {
            "metric": "gateway_proxy_requests_per_sec",
            "value": round(done / elapsed, 1),
            "unit": "req/s",
            "vs_baseline": round(done / elapsed / 170600.51, 5),
            "requests": done,
            "errors": errors,
            "seconds": round(elapsed, 2),
            "concurrency": concurrency,
            "p50_ms": round(1000 * pct(0.50), 2),
            "p90_ms": round(1000 * pct(0.90), 2),
            "p99_ms": round(1000 * pct(0.99), 2),
            "goodput": goodput,
            "prometheus": prom,
            "native_router": gw.state.load_manager.stats().get(
                "native_router", False
            ),
        }
    finally:
        await upstream.stop()
        await gw.close()


async def run_prefix_bench(requests: int) -> dict:
    """Shared-prefix workload against a real tpu:// engine (CPU backend)
    proxied through the full gateway: repeated system prompt, varying tails.
    Sequential on purpose — each request is classified hit/miss from the
    engine's prefix counters, so TTFT can be split by cache outcome."""
    import aiohttp
    from aiohttp.test_utils import TestServer

    from llmlb_tpu.engine.server import create_engine_app
    from llmlb_tpu.engine.service import Engine
    from tests.support import GatewayHarness

    engine = Engine.from_preset(
        "debug-tiny", num_slots=4, slot_capacity=256,
        prefill_buckets=(16, 32, 64),
    )
    eng_server = TestServer(create_engine_app(engine, owns_engine=False))
    await eng_server.start_server()
    gw = await GatewayHarness.create()
    try:
        gw.register_mock(
            f"http://127.0.0.1:{eng_server.port}", [engine.model_id]
        )
        headers = dict(await gw.inference_headers())
        # ~130 byte-tokens of shared head, well past the 16-token min prefix
        system = ("You are the TPU serving assistant. Answer briefly and "
                  "cite the runbook section when relevant. ") * 2
        metrics = engine.core.metrics

        ttft_hit: list[float] = []
        ttft_miss: list[float] = []
        for i in range(requests):
            payload = {
                "model": engine.model_id,
                "messages": [
                    {"role": "system", "content": system},
                    {"role": "user", "content": f"Question {i}: status of "
                                                f"pool {i % 7}?"},
                ],
                "max_tokens": 8, "temperature": 0.0, "stream": True,
            }
            hits_before = metrics.prefix_hits_total
            t0 = time.perf_counter()
            ttft = None
            resp = await gw.client.post("/v1/chat/completions", json=payload,
                                        headers=headers)
            assert resp.status == 200, await resp.text()
            async for raw in resp.content:
                line = raw.decode(errors="replace").strip()
                if not line.startswith("data: ") or line == "data: [DONE]":
                    continue
                chunk = json.loads(line[len("data: "):])
                if ttft is None and any(
                    c.get("delta", {}).get("content")
                    for c in chunk.get("choices", [])
                ):
                    ttft = time.perf_counter() - t0
            await resp.release()
            if ttft is None:
                continue
            if metrics.prefix_hits_total > hits_before:
                ttft_hit.append(ttft)
            else:
                ttft_miss.append(ttft)

        # cross-check the Prometheus exposition carries the same counters
        async with aiohttp.ClientSession() as s:
            async with s.get(
                f"http://127.0.0.1:{eng_server.port}/metrics"
            ) as r:
                exposition = await r.text()
        assert "llmlb_engine_prefix_cache_hits_total" in exposition

        hits = metrics.prefix_hits_total
        misses = metrics.prefix_misses_total
        cached = metrics.prefix_cached_tokens_total
        # actual shared token head between any two requests of this
        # workload, aligned down to the engine's prefix quantum — the
        # denominator for "what fraction of shareable tokens came from cache"
        ids = [engine.encode_chat([
            {"role": "system", "content": system},
            {"role": "user", "content": f"Question {i}: status of "
                                        f"pool {i % 7}?"},
        ]) for i in (0, 1)]
        lcp = 0
        while (lcp < min(len(ids[0]), len(ids[1]))
               and ids[0][lcp] == ids[1][lcp]):
            lcp += 1
        align = engine.core.prefix_align or 1
        shared_est = max(1, (requests - 1) * ((lcp // align) * align))

        def mean(xs):
            return round(sum(xs) / len(xs) * 1000, 2) if xs else None

        return {
            "metric": "prefix_cache_shared_prefix_workload",
            "requests": requests,
            "prefix_hits": hits,
            "prefix_misses": misses,
            "hit_rate": round(hits / max(1, hits + misses), 3),
            "prefill_tokens_saved": cached,
            "shared_tokens_hit_fraction": round(cached / shared_est, 3),
            "ttft_hit_mean_ms": mean(ttft_hit),
            "ttft_miss_mean_ms": mean(ttft_miss),
            "engine_prefix_cache": engine.core.prefix_cache_info(),
        }
    finally:
        await gw.close()
        await eng_server.close()
        engine.shutdown()


async def run_quantized_bench(requests_n: int) -> dict:
    """Int8-KV occupancy and throughput at EQUAL HBM budget
    (docs/quantization.md). Three engines, identical except the
    `--quantize` knob: bf16 baseline, int8 KV pages, int8 weights+KV.
    The quantized pools get as many pages as the bf16 pool's BYTES buy
    (bytes_per_page is ~(D+4)/2D of bf16, so ~1.9x the pages), and a
    saturating swarm of identical short chats measures peak concurrent
    sequences per budget. Also reports decode tok/s and a
    greedy output-divergence sample (int8 vs bf16 token streams on the
    same prompts)."""
    import dataclasses as dc
    import random

    import jax.numpy as jnp

    from llmlb_tpu.engine.presets import get_preset
    from llmlb_tpu.engine.scheduler import SamplingParams, kv_page_bytes
    from llmlb_tpu.engine.service import Engine
    from llmlb_tpu.engine.tokenizer import ByteTokenizer
    from llmlb_tpu.engine.scheduler import EngineCore

    # head_dim 64 at bf16 — the serving-shaped cell: int8 page bytes are
    # (64+4)/(64·2) = 53% of bf16, so one HBM budget holds ~1.88x pages
    cfg = dc.replace(
        get_preset("debug-tiny"), hidden_size=256, num_heads=4,
        num_kv_heads=2, intermediate_size=512, dtype=jnp.bfloat16,
    )
    capacity, page = 64, 16
    bf16_pages = 33  # 32 usable + trash page: the HBM budget
    budget_bytes = bf16_pages * kv_page_bytes(cfg, page, quantized=False)
    int8_pages = budget_bytes // kv_page_bytes(cfg, page, quantized=True)
    # 28-token prompts reserve BOTH of a request's pages at admission
    # (prompt+gen stays inside 2 pages), so peak concurrency is bounded by
    # the pool, not by decode-growth cuts — the quantity under test
    prompt_len, gen = 28, 3

    r = random.Random(0)
    prompts = [[r.randrange(1, cfg.vocab_size)
                for _ in range(prompt_len)] for _ in range(requests_n)]
    divergence_prompts = prompts[:4]

    results: dict = {}
    baseline_tokens: list[list[int]] | None = None
    for mode in ("bf16", "int8-kv", "int8-all"):
        quantize = {"bf16": "off", "int8-kv": "kv", "int8-all": "all"}[mode]
        pages = bf16_pages if mode == "bf16" else int(int8_pages)
        core = EngineCore(
            cfg, num_slots=32, slot_capacity=capacity,
            prefill_buckets=(16,), seed=0, kv_page_size=page,
            kv_pages=pages, quantize=quantize, prefix_cache=False,
        )
        core.start()
        engine = Engine("quant-bench", core, ByteTokenizer(cfg.vocab_size))
        try:
            peak = 0
            done = False

            async def sample() -> None:
                nonlocal peak
                while not done:
                    peak = max(peak, core.stats().active_slots)
                    await asyncio.sleep(0.002)

            sampler = asyncio.create_task(sample())
            t0 = time.perf_counter()
            outs = await asyncio.gather(*(
                engine.complete(p, SamplingParams(temperature=0.0,
                                                  max_tokens=gen))
                for p in prompts
            ))
            elapsed = time.perf_counter() - t0
            done = True
            await sampler

            # greedy divergence sample vs the bf16 streams
            sample_tokens = []
            for p in divergence_prompts:
                req_toks = []
                async for delta in engine.stream(
                    p, SamplingParams(temperature=0.0, max_tokens=8)
                ):
                    req_toks.append(delta.text)
                sample_tokens.append("".join(req_toks))
            if baseline_tokens is None:
                baseline_tokens = sample_tokens
                diverged = 0.0
            else:
                diverged = sum(
                    1 for a, b in zip(baseline_tokens, sample_tokens)
                    if a != b
                ) / len(sample_tokens)

            completion_tokens = sum(o.completion_tokens for o in outs)
            info = core.kv_cache_info()
            results[mode] = {
                "quantize": quantize,
                "kv_dtype": info["kv_dtype"],
                "pages_total": info["pages_total"],
                "bytes_per_page": info["bytes_per_page"],
                "kv_hbm_bytes": info["hbm_bytes"],
                "peak_concurrent_sequences": peak,
                "decode_tokens_per_sec": round(
                    completion_tokens / elapsed, 1
                ),
                "seconds": round(elapsed, 2),
                "finished": sum(
                    1 for o in outs
                    if o.finish_reason in ("stop", "length")
                ),
                "output_divergence_sample": round(diverged, 3),
                "param_bytes": core.quant_info()["param_bytes"],
            }
        finally:
            engine.shutdown()

    bf16_b = results["bf16"]["kv_hbm_bytes"]
    kv_b = results["int8-kv"]["kv_hbm_bytes"]
    return {
        "metric": "quantized_equal_hbm_budget",
        "requests": requests_n,
        "hbm_budget_bytes": budget_bytes,
        # pools match the budget within one page's rounding
        "equal_hbm_budget": abs(kv_b - bf16_b) <= results["int8-kv"][
            "bytes_per_page"
        ],
        "peak_concurrency_gain_int8_kv": round(
            results["int8-kv"]["peak_concurrent_sequences"]
            / max(1, results["bf16"]["peak_concurrent_sequences"]), 2
        ),
        "bytes_per_page_ratio": round(
            results["int8-kv"]["bytes_per_page"]
            / results["bf16"]["bytes_per_page"], 3
        ),
        "bf16": results["bf16"],
        "int8_kv": results["int8-kv"],
        "int8_all": results["int8-all"],
    }


async def run_structured_bench(requests: int) -> dict:
    """Structured-outputs workload: mixed schema-constrained + free-form
    traffic through the full gateway against a real tpu:// engine (CPU
    backend). Asserts 100% schema-valid JSON on every constrained response
    and reports the TTFT/TPS overhead of constrained decoding vs the
    free-form baseline, plus compile-cache effectiveness (second and later
    requests with the same schema must skip DFA construction)."""
    import jsonschema
    from aiohttp.test_utils import TestServer

    from llmlb_tpu.engine.server import create_engine_app
    from llmlb_tpu.engine.service import Engine
    from tests.support import GatewayHarness

    schema = {
        "type": "object",
        "properties": {
            "city": {"enum": ["sf", "nyc", "tokyo"]},
            "celsius": {"type": "boolean"},
            "temp": {"type": "integer"},
        },
        "required": ["city", "celsius", "temp"],
    }
    engine = Engine.from_preset(
        "debug-tiny", model_id="bench-structured", num_slots=4,
        slot_capacity=256, prefill_buckets=(16, 32, 64),
    )
    eng_server = TestServer(create_engine_app(engine, owns_engine=False))
    await eng_server.start_server()
    gw = await GatewayHarness.create()
    try:
        from llmlb_tpu.gateway.types import Capability

        gw.register_mock(
            f"http://127.0.0.1:{eng_server.port}", [engine.model_id],
            capabilities=[Capability.CHAT_COMPLETION,
                          Capability.STRUCTURED_OUTPUTS],
        )
        headers = dict(await gw.inference_headers())

        async def one(i: int, constrained: bool) -> dict:
            payload = {
                "model": engine.model_id,
                "messages": [{"role": "user",
                              "content": f"weather report {i}"}],
                "max_tokens": 96, "temperature": 1.0, "stream": True,
            }
            if constrained:
                payload["response_format"] = {
                    "type": "json_schema",
                    "json_schema": {"name": "weather", "schema": schema},
                }
            t0 = time.perf_counter()
            ttft = None
            text = ""
            finish = None
            tokens = 0
            resp = await gw.client.post("/v1/chat/completions", json=payload,
                                        headers=headers)
            assert resp.status == 200, await resp.text()
            async for raw in resp.content:
                line = raw.decode(errors="replace").strip()
                if not line.startswith("data: ") or line == "data: [DONE]":
                    continue
                chunk = json.loads(line[len("data: "):])
                for c in chunk.get("choices", []):
                    delta = c.get("delta", {})
                    if delta.get("content"):
                        if ttft is None:
                            ttft = time.perf_counter() - t0
                        text += delta["content"]
                    if c.get("finish_reason"):
                        finish = c["finish_reason"]
                usage = chunk.get("usage")
                if usage:
                    tokens = usage.get("completion_tokens", 0)
            await resp.release()
            return {"ttft": ttft, "e2e": time.perf_counter() - t0,
                    "text": text, "finish": finish, "tokens": tokens}

        # XLA-warm the engine with free-form traffic first, so the cold
        # constrained request below isolates the SCHEMA compile cost rather
        # than the first-ever prefill/decode compile.
        for _ in range(2):
            await one(0, False)

        # cold first constrained request pays the schema compile; capture it
        # separately so the cache-effectiveness claim is measurable
        cold = await one(0, True)
        jsonschema.validate(json.loads(cold["text"]), schema)
        metrics = engine.core.metrics

        results = {"constrained": [], "free": []}
        valid = 1
        for i in range(1, requests):
            constrained = i % 2 == 0
            r = await one(i, constrained)
            if constrained:
                obj = json.loads(r["text"])  # must parse...
                jsonschema.validate(obj, schema)  # ...and validate
                assert r["finish"] == "stop", r["finish"]
                valid += 1
                results["constrained"].append(r)
            else:
                results["free"].append(r)

        def mean_ms(rows, key):
            vals = [r[key] for r in rows if r[key] is not None]
            return round(sum(vals) / len(vals) * 1000, 2) if vals else None

        def tps(rows):
            toks = sum(r["tokens"] for r in rows)
            secs = sum(r["e2e"] for r in rows)
            return round(toks / secs, 1) if secs else None

        info = engine.core.structured_info()
        compile_p50 = metrics.schema_compile.percentile(50) or 0.0
        warm_ttft = mean_ms(results["constrained"], "ttft")
        free_ttft = mean_ms(results["free"], "ttft")
        constrained_n = len(results["constrained"]) + 1
        return {
            "metric": "structured_outputs_mixed_workload",
            "requests": requests,
            "constrained_requests": constrained_n,
            "schema_valid": valid,
            "schema_valid_fraction": round(valid / constrained_n, 3),
            "ttft_constrained_cold_ms": round(cold["ttft"] * 1000, 2)
            if cold["ttft"] else None,
            "ttft_constrained_warm_mean_ms": warm_ttft,
            "ttft_free_mean_ms": free_ttft,
            "ttft_constraint_overhead_ms": (
                round(warm_ttft - free_ttft, 2)
                if warm_ttft is not None and free_ttft is not None else None
            ),
            "tps_constrained": tps(results["constrained"]),
            "tps_free": tps(results["free"]),
            "schema_compile_p50_ms": round(compile_p50 * 1000, 2),
            # cache effectiveness: >0 hits means repeat schemas skipped DFA
            # construction; warm added TTFT must undercut one compile
            "compile_cache_hits": info["compile_cache_hits"],
            "compile_cache_misses": info["compile_cache_misses"],
            "warm_overhead_under_compile_time": (
                warm_ttft is not None and free_ttft is not None
                and (warm_ttft - free_ttft) < max(compile_p50 * 1000, 1e-9)
            ) if compile_p50 else None,
            "mask_cache_bytes": info["mask_cache_bytes"],
            "constraint_violations": metrics.constraint_violations_total,
            "masked_decode_steps": metrics.masked_decode_steps_total,
            "engine_structured": info,
        }
    finally:
        await gw.close()
        await eng_server.close()
        engine.shutdown()


async def run_spec_bench(requests: int) -> dict:
    """Speculative-decoding workload: predictable continuations (shared-
    prefix chat + JSON-mode structured output) through the full gateway
    against a real tpu:// engine (CPU backend), run twice — speculation on
    and off — on otherwise identical engines. Reports drafted/accepted
    tokens, acceptance rate, and decode tok/s for both modes; the JSON-mode
    half must stay 100% schema-valid under speculation."""
    import jsonschema
    from aiohttp.test_utils import TestServer

    from llmlb_tpu.engine.server import create_engine_app
    from llmlb_tpu.engine.service import Engine
    from llmlb_tpu.gateway.types import Capability
    from tests.support import GatewayHarness

    # An array of identical items: grammar + greedy decode make the
    # continuation maximally predictable — the structured shape speculation
    # exists to accelerate (acceptance approaches 1).
    schema = {"type": "array", "items": {"enum": ["aa"]},
              "minItems": 20, "maxItems": 20}
    system = ("You are the TPU serving assistant. Answer briefly and "
              "cite the runbook section when relevant. ") * 2

    async def run_mode(spec: bool) -> dict:
        engine = Engine.from_preset(
            "debug-tiny", model_id="bench-spec", num_slots=4,
            slot_capacity=512, prefill_buckets=(16, 32, 64),
            spec_decode=spec, spec_max_draft=6,
        )
        eng_server = TestServer(create_engine_app(engine, owns_engine=False))
        await eng_server.start_server()
        gw = await GatewayHarness.create()
        try:
            gw.register_mock(
                f"http://127.0.0.1:{eng_server.port}", [engine.model_id],
                capabilities=[Capability.CHAT_COMPLETION,
                              Capability.STRUCTURED_OUTPUTS],
            )
            headers = dict(await gw.inference_headers())

            async def one(i: int, constrained: bool) -> dict:
                payload = {
                    "model": engine.model_id,
                    "messages": [
                        {"role": "system", "content": system},
                        {"role": "user",
                         "content": f"question {i}: 1 2 3 4 5 6 7 8"},
                    ],
                    "max_tokens": 140, "temperature": 0.0, "stream": True,
                }
                if constrained:
                    payload["response_format"] = {
                        "type": "json_schema",
                        "json_schema": {"name": "items", "schema": schema},
                    }
                t0 = time.perf_counter()
                ttft = None
                text = ""
                tokens = 0
                resp = await gw.client.post("/v1/chat/completions",
                                            json=payload, headers=headers)
                assert resp.status == 200, await resp.text()
                async for raw in resp.content:
                    line = raw.decode(errors="replace").strip()
                    if not line.startswith("data: ") or line == "data: [DONE]":
                        continue
                    chunk = json.loads(line[len("data: "):])
                    for c in chunk.get("choices", []):
                        if c.get("delta", {}).get("content"):
                            if ttft is None:
                                ttft = time.perf_counter() - t0
                            text += c["delta"]["content"]
                    usage = chunk.get("usage")
                    if usage:
                        tokens = usage.get("completion_tokens", 0)
                await resp.release()
                e2e = time.perf_counter() - t0
                if constrained:
                    jsonschema.validate(json.loads(text), schema)
                return {"tokens": tokens,
                        "decode_s": max(1e-9, e2e - (ttft or 0.0))}

            # XLA warmup outside the timed window (incl. one of each shape)
            await one(0, False)
            await one(0, True)

            t0 = time.perf_counter()
            rows = await asyncio.gather(*(
                one(i, i % 2 == 0) for i in range(requests)
            ))
            wall = time.perf_counter() - t0
            m = engine.core.metrics
            tokens = sum(r["tokens"] for r in rows)
            decode_s = sum(r["decode_s"] for r in rows)
            drafted = m.spec_draft_tokens_total
            return {
                "spec_decode": spec,
                "requests": requests,
                "completion_tokens": tokens,
                "wall_s": round(wall, 2),
                "tok_per_s_wall": round(tokens / wall, 1),
                # per-request decode time excludes each request's TTFT
                # (prefill), summed across the concurrent batch
                "decode_tok_per_s": round(tokens / decode_s, 1),
                "verify_steps": m.spec_verify_steps_total,
                "drafted_tokens": drafted,
                "accepted_tokens": m.spec_accepted_tokens_total,
                "emitted_tokens": m.spec_emitted_tokens_total,
                "acceptance_rate": (
                    round(m.spec_accepted_tokens_total / drafted, 3)
                    if drafted else None
                ),
                "constraint_violations": m.constraint_violations_total,
                "engine_spec": engine.core.spec_info(),
            }
        finally:
            await gw.close()
            await eng_server.close()
            engine.shutdown()

    off = await run_mode(False)
    on = await run_mode(True)
    assert off["verify_steps"] == 0  # speculation off: path never dispatches
    return {
        "metric": "spec_decode_workload",
        "requests": requests,
        "speedup_wall": round(on["tok_per_s_wall"] / off["tok_per_s_wall"], 2),
        "speedup_decode": round(
            on["decode_tok_per_s"] / off["decode_tok_per_s"], 2
        ),
        "acceptance_rate": on["acceptance_rate"],
        "spec_on": on,
        "spec_off": off,
    }


async def run_lora_bench(requests: int) -> dict:
    """Multi-LoRA workload (docs/lora.md): a mixed-adapter request stream
    (3 adapters + adapter-free traffic) through the FULL gateway against a
    real tpu:// engine (CPU backend), two ways:

    - batched: all requests concurrent — the bgmv path decodes the mixed
      batch together, every adapter stays resident (pool of 4);
    - naive: the one-adapter-at-a-time swapping baseline — the engine's
      pool holds ONE adapter and requests run strictly in arrival order,
      so every adapter switch in the interleaved stream evicts and
      reloads (what serving N tenants looks like on a server that must
      swap the single active adapter instead of batching them).

    Reports decode tok/s, wall-clock, per-request latency, adapter cache
    hit rate (1 - loads/adapter_requests), and asserts the two modes'
    greedy outputs are token-identical (batching must not change any
    tenant's stream).

    CPU-host honesty (the BENCH_r09 throughput stance): on a CPU backend
    decode compute scales ~linearly with batch width, so batching buys no
    wall-clock here and the committed transferable evidence is structural —
    device dispatches per served token (batched runs ~6x fewer programs;
    on TPU, where a wider decode step costs ~the same HBM sweep, that IS
    the speedup) and the adapter cache hit rate (the naive server reloads
    an adapter on nearly every switch)."""
    import tempfile

    from aiohttp.test_utils import TestServer

    from llmlb_tpu.engine.presets import get_preset
    from llmlb_tpu.engine.server import create_engine_app
    from llmlb_tpu.engine.service import Engine
    from llmlb_tpu.gateway.types import Capability, EndpointType
    from llmlb_tpu.lora import save_adapter
    from tests.support import GatewayHarness

    adapters = ("acme", "globex", "initech")
    lora_dir = tempfile.mkdtemp(prefix="bench-lora-")
    cfg = get_preset("debug-tiny")
    for name in adapters:
        save_adapter(lora_dir, name, cfg, rank=8)

    # request plan: round-robin across 3 adapters + adapter-free rows
    plan = [(adapters[i % 4] if i % 4 < 3 else None)
            for i in range(requests)]
    gen_tokens = 24

    async def run_mode(label: str, max_adapters: int,
                       serialize: bool) -> dict:
        engine = Engine.from_preset(
            "debug-tiny", model_id="bench-lora", num_slots=8,
            slot_capacity=128, prefill_buckets=(16, 32), seed=0,
            lora_dir=lora_dir, lora_max_adapters=max_adapters,
        )
        eng_server = TestServer(create_engine_app(engine,
                                                  owns_engine=False))
        await eng_server.start_server()
        gw = await GatewayHarness.create()
        try:
            gw.register_mock(
                f"http://127.0.0.1:{eng_server.port}", [engine.model_id],
                endpoint_type=EndpointType.TPU,
                capabilities=[Capability.CHAT_COMPLETION, Capability.LORA],
            )
            headers = dict(await gw.inference_headers())

            async def one(i: int, adapter: str | None) -> dict:
                payload = {
                    "model": engine.model_id,
                    # ONE prompt for every tenant: output differences are
                    # then purely the adapters' doing (distinctness check)
                    "messages": [{"role": "user",
                                  "content": "ticket escalation report"}],
                    "max_tokens": gen_tokens, "temperature": 0.0,
                }
                if adapter is not None:
                    payload["lora"] = adapter
                t_req = time.perf_counter()
                resp = await gw.client.post("/v1/chat/completions",
                                            json=payload, headers=headers)
                assert resp.status == 200, await resp.text()
                body = await resp.json()
                return {
                    "adapter": adapter,
                    "text": body["choices"][0]["message"]["content"],
                    "tokens": body["usage"]["completion_tokens"],
                    "e2e_s": time.perf_counter() - t_req,
                }

            core = engine.core
            peak = 0
            done = False

            async def sample() -> None:
                nonlocal peak
                while not done:
                    peak = max(peak, core.stats().active_slots)
                    await asyncio.sleep(0.002)

            sampler = asyncio.create_task(sample())
            steps0 = core.metrics.decode_step.n
            t0 = time.perf_counter()
            if serialize:
                # one adapter at a time, ARRIVAL order: every adapter
                # switch in the interleaved stream swaps the pool's single
                # slot (evict + disk->device reload) before decoding
                outs = [await one(i, a) for i, a in enumerate(plan)]
            else:
                outs = list(await asyncio.gather(*(
                    one(i, a) for i, a in enumerate(plan)
                )))
            elapsed = time.perf_counter() - t0
            done = True
            await sampler

            adapter_requests = sum(1 for a in plan if a is not None)
            loads = core.metrics.lora_loads_total
            completion = sum(o["tokens"] for o in outs)
            lat = sorted(o["e2e_s"] for o in outs)
            return {
                "request_latency_mean_s": round(
                    sum(lat) / len(lat), 3
                ),
                "request_latency_p99_s": round(
                    lat[min(len(lat) - 1, int(0.99 * len(lat)))], 3
                ),
                "label": label,
                "requests": len(outs),
                "seconds": round(elapsed, 2),
                "decode_tokens_per_sec": round(completion / elapsed, 1),
                "decode_dispatches": core.metrics.decode_step.n - steps0,
                "peak_concurrent_sequences": peak,
                "adapter_requests": adapter_requests,
                "adapter_loads": loads,
                "adapter_evictions": core.metrics.lora_evictions_total,
                "adapter_cache_hit_rate": round(
                    1.0 - loads / max(1, adapter_requests), 3
                ),
                "gateway_lora_requests":
                    gw.state.metrics.summary()["lora_requests_total"],
                "outputs": {o["adapter"] or "": o["text"] for o in outs},
            }
        finally:
            await gw.close()
            await eng_server.close()
            engine.shutdown()

    batched = await run_mode("batched", max_adapters=4, serialize=False)
    naive = await run_mode("naive-swap", max_adapters=1, serialize=True)

    # tenant-stream integrity: batching must not change any output, and
    # the adapters must actually produce distinct streams on one prompt
    # (else everything above is vacuous)
    identical = batched["outputs"] == naive["outputs"]
    distinct = len(set(batched["outputs"].values())) == len(adapters) + 1
    for mode in (batched, naive):
        mode.pop("outputs")
    return {
        "metric": "lora_mixed_adapter_workload",
        "requests": requests,
        "adapters": len(adapters),
        "outputs_token_identical_across_modes": identical,
        "adapters_distinct": distinct,
        "wall_clock_speedup": round(
            naive["seconds"] / max(1e-9, batched["seconds"]), 2
        ),
        "decode_tps_ratio": round(
            batched["decode_tokens_per_sec"]
            / max(1e-9, naive["decode_tokens_per_sec"]), 2
        ),
        "batched": batched,
        "naive": naive,
        "dispatch_reduction": round(
            naive["decode_dispatches"]
            / max(1, batched["decode_dispatches"]), 2
        ),
        "cpu_host_caveat": (
            "wall-clock unjudgeable on a CPU backend: decode compute "
            "scales ~linearly with batch width, so batching cannot win "
            "here; the transferable figures are dispatch_reduction and "
            "adapter_cache_hit_rate (see docstring)"
        ),
        "passed": bool(
            identical and distinct
            and batched["adapter_cache_hit_rate"]
            > naive["adapter_cache_hit_rate"]
            and batched["decode_dispatches"] < naive["decode_dispatches"]
        ),
    }


async def run_fused_bench(requests: int) -> dict:
    """Fused-decode workload (docs/fused-decode.md): mixed traffic — plain
    chat, LoRA-adapter, JSON-schema-constrained, all with speculation and
    int8 KV on — through the FULL gateway against a real tpu:// engine
    (CPU backend), twice on identical engines: LLMLB_FUSED_DECODE on vs
    off. Reports decode tok/s and, the transferable figure, the per-step
    device dispatch count from the scheduler's ledger: fused must hold
    exactly 1.0 per decode/verify step while legacy runs 3-5, and greedy
    outputs must be token-identical across modes.

    CPU-host honesty (the BENCH_r09 stance): XLA:CPU fuses the whole step
    into host code either way, so dispatch overhead here is Python-sized
    and wall-clock gains are noise; the committed evidence is structural —
    dispatches per step and zero constrained single-step fallbacks. On
    TPU each dispatch is a host->device launch + its H2D/D2H syncs, and
    the per-step count IS the latency story."""
    import tempfile

    import jsonschema
    from aiohttp.test_utils import TestServer

    from llmlb_tpu.engine.presets import get_preset
    from llmlb_tpu.engine.server import create_engine_app
    from llmlb_tpu.engine.service import Engine
    from llmlb_tpu.gateway.types import Capability, EndpointType
    from llmlb_tpu.lora import save_adapter
    from tests.support import GatewayHarness

    lora_dir = tempfile.mkdtemp(prefix="bench-fused-")
    save_adapter(lora_dir, "acme", get_preset("debug-tiny"), rank=8)

    # array-of-identical-items schema: grammar + greedy decode make the
    # constrained continuation predictable, so speculation engages on the
    # constrained rows too (the 4-feature-on shape this PR fuses)
    schema = {"type": "array", "items": {"enum": ["aa"]},
              "minItems": 8, "maxItems": 8}
    system = "You are the TPU serving assistant. Answer briefly. " * 2
    # request plan by i % 3: plain chat, LoRA adapter, JSON-constrained
    kinds = [("plain", "lora", "json")[i % 3] for i in range(requests)]

    async def run_mode(fused: bool) -> dict:
        engine = Engine.from_preset(
            "debug-tiny", model_id="bench-fused", num_slots=4,
            slot_capacity=256, prefill_buckets=(16, 32, 64), seed=0,
            quantize="kv", lora_dir=lora_dir, spec_decode=True,
            spec_max_draft=4, fused_decode=fused,
        )
        eng_server = TestServer(create_engine_app(engine,
                                                  owns_engine=False))
        await eng_server.start_server()
        gw = await GatewayHarness.create()
        try:
            gw.register_mock(
                f"http://127.0.0.1:{eng_server.port}", [engine.model_id],
                endpoint_type=EndpointType.TPU,
                capabilities=[Capability.CHAT_COMPLETION,
                              Capability.STRUCTURED_OUTPUTS,
                              Capability.LORA],
            )
            headers = dict(await gw.inference_headers())

            async def one(i: int, kind: str) -> dict:
                payload = {
                    "model": engine.model_id,
                    "messages": [
                        {"role": "system", "content": system},
                        {"role": "user",
                         "content": f"question {i}: 1 2 3 4 5 6 7 8"},
                    ],
                    "max_tokens": 64, "temperature": 0.0,
                }
                if kind == "lora":
                    payload["lora"] = "acme"
                elif kind == "json":
                    payload["response_format"] = {
                        "type": "json_schema",
                        "json_schema": {"name": "items", "schema": schema},
                    }
                resp = await gw.client.post("/v1/chat/completions",
                                            json=payload, headers=headers)
                assert resp.status == 200, await resp.text()
                body = await resp.json()
                text = body["choices"][0]["message"]["content"]
                if kind == "json":
                    jsonschema.validate(json.loads(text), schema)
                return {"text": text,
                        "tokens": body["usage"]["completion_tokens"]}

            # XLA warmup outside the timed window, one of each shape
            for kind in ("plain", "lora", "json"):
                await one(-1, kind)

            t0 = time.perf_counter()
            outs = list(await asyncio.gather(*(
                one(i, k) for i, k in enumerate(kinds)
            )))
            elapsed = time.perf_counter() - t0

            m = engine.core.metrics
            records = engine.core.step_stats.snapshot(limit=512)["records"]
            decs = [r for r in records
                    if r["kind"] in ("decode", "verify")]
            per_step = [r["dispatches"] for r in decs] or [0]
            completion = sum(o["tokens"] for o in outs)
            return {
                "fused": fused,
                "requests": len(outs),
                "seconds": round(elapsed, 2),
                "completion_tokens": completion,
                "decode_tokens_per_sec": round(completion / elapsed, 1),
                "decode_steps_observed": len(decs),
                "dispatches_per_step_mean": round(
                    sum(per_step) / len(per_step), 2),
                "dispatches_per_step_max": max(per_step),
                "decode_dispatches_total": m.decode_dispatches_total,
                "fused_decode_steps_total": m.fused_decode_steps_total,
                "constrained_burst_fallbacks":
                    m.constrained_burst_fallback_total,
                "masked_decode_steps": m.masked_decode_steps_total,
                "spec_verify_steps": m.spec_verify_steps_total,
                "spec_acceptance_rate": (
                    round(m.spec_accepted_tokens_total
                          / m.spec_draft_tokens_total, 3)
                    if m.spec_draft_tokens_total else None
                ),
                "outputs": {i: o["text"] for i, o in enumerate(outs)},
            }
        finally:
            await gw.close()
            await eng_server.close()
            engine.shutdown()

    on = await run_mode(True)
    off = await run_mode(False)
    identical = on["outputs"] == off["outputs"]
    for mode in (on, off):
        mode.pop("outputs")
    return {
        "metric": "fused_decode_workload",
        "requests": requests,
        "outputs_token_identical_across_modes": identical,
        "dispatch_reduction_per_step": round(
            off["dispatches_per_step_mean"]
            / max(1e-9, on["dispatches_per_step_mean"]), 2
        ),
        "decode_tps_ratio": round(
            on["decode_tokens_per_sec"]
            / max(1e-9, off["decode_tokens_per_sec"]), 2
        ),
        "fused_on": on,
        "fused_off": off,
        "cpu_host_caveat": (
            "wall-clock unjudgeable on a CPU backend: XLA:CPU dispatch "
            "overhead is Python-sized, so collapsing dispatches cannot "
            "show up in tok/s here; the transferable figures are "
            "dispatches_per_step (fused holds exactly 1) and zero "
            "constrained_burst_fallbacks (see docstring)"
        ),
        "passed": bool(
            identical
            and on["dispatches_per_step_max"] == 1
            and on["constrained_burst_fallbacks"] == 0
            and on["masked_decode_steps"] > 0
            and on["spec_verify_steps"] > 0
            and off["dispatches_per_step_mean"] > 1.0
        ),
    }


async def _make_named_key(gw, name: str) -> str:
    """A second inference API key so the slo-mix workload has distinct
    tenants (rate-limit overrides key by API-key name)."""
    resp = await gw.client.post(
        "/api/api-keys",
        json={"name": name,
              "permissions": ["openai.inference", "openai.models.read"]},
        headers=await gw.admin_headers(),
    )
    assert resp.status == 201, await resp.text()
    return (await resp.json())["api_key"]


def _gap_stats(gaps: list[float]) -> dict:
    """p50/p99/max over inter-token gaps, plus the fraction of gaps that
    would blow a 250 ms ITL target — the per-gap view a mean hides."""
    if not gaps:
        return {"n": 0}
    s = sorted(gaps)
    return {
        "n": len(s),
        "p50_ms": round(s[len(s) // 2] * 1000, 1),
        "p99_ms": round(s[min(len(s) - 1, int(len(s) * 0.99))] * 1000, 1),
        "max_ms": round(s[-1] * 1000, 1),
        "frac_over_250ms": round(
            sum(1 for g in s if g > 0.25) / len(s), 4
        ),
    }


async def run_slo_mix_bench(requests: int) -> dict:
    """SLO-mix workload (docs/scheduling.md): the adversarial tenant mix
    overload protection exists for, through the full gateway against a real
    tpu:// engine (CPU backend). Three labeled sub-scenarios matching the
    acceptance bar:

    (a) itl_bound — background streams decode while a batch of long
        prompts (the CPU-scaled stand-in for a 128k arrival; debug-tiny
        caps positions at 512) prefills, with the chunk budget off vs on.
        Reports client-measured inter-token gap p99/max for the background
        decoders: off shows the prefill spike, on bounds it.
    (b) ratelimit — one greedy API key fires concurrent waves against a
        per-key token bucket while a background tenant trickles requests:
        the greedy key's excess 429s with honest Retry-After, the
        background tenant's goodput holds at 1.0.
    (c) preemption — a low-priority stream on a single-slot engine is
        parked by a high-priority arrival and resumes; its final text must
        be identical to an uninterrupted reference run.

    Goodput (PR 6's SLO machinery, by priority class) is the reported
    figure, not raw throughput. Wall-clock numbers are CPU-host bound and
    not TPU-transferable; the mechanisms (chunk interleaving, bucket math,
    park/resume identity) are.
    """
    from aiohttp.test_utils import TestServer

    from llmlb_tpu.engine.server import create_engine_app
    from llmlb_tpu.engine.service import Engine
    from llmlb_tpu.gateway.config import RateLimitConfig
    from llmlb_tpu.gateway.ratelimit import RateLimiter
    from tests.support import GatewayHarness

    LONG_CHARS = 420  # ByteTokenizer: ~1 token/char; slot capacity is 512
    CHUNK_BUDGET = 16
    # Prompts probed to decode long (no early EOS) under the seed-0 random
    # weights — greedy on a random tiny model stops whenever EOS wins the
    # argmax, so background decoders must be prompts that keep emitting.
    BG_PROMPTS = (
        "background chat 0", "background chat 3",
        "lorem ipsum dolor sit amet", "alpha bravo charlie delta",
    )

    async def stream_chat(gw, headers, content, *, priority, max_tokens,
                          marks: list | None = None) -> dict:
        """One streaming chat; records the arrival time of every content
        delta into `marks` (client-side ITL ground truth)."""
        payload = {
            "model": "bench-slo",
            "messages": [{"role": "user", "content": content}],
            "max_tokens": max_tokens, "temperature": 0.0, "stream": True,
            "priority": priority,
        }
        t0 = time.perf_counter()
        text, ttft = "", None
        resp = await gw.client.post("/v1/chat/completions", json=payload,
                                    headers=headers)
        assert resp.status == 200, await resp.text()
        async for raw in resp.content:
            line = raw.decode(errors="replace").strip()
            if not line.startswith("data: ") or line == "data: [DONE]":
                continue
            chunk = json.loads(line[len("data: "):])
            for c in chunk.get("choices", []):
                delta = c.get("delta", {}).get("content")
                if delta:
                    now = time.perf_counter()
                    if ttft is None:
                        ttft = now - t0
                    if marks is not None:
                        marks.append(now)
                    text += delta
        await resp.release()
        return {"text": text, "ttft_s": ttft}

    # ---------------------------------------------- (a) ITL bound on/off
    async def itl_mode(budget: int) -> dict:
        engine = Engine.from_preset(
            "debug-tiny", model_id="bench-slo", num_slots=8,
            slot_capacity=512, prefill_buckets=(16, 32, 64, 128, 256),
            kv_page_size=16, seed=0,
            prefill_chunk_budget=budget, prefix_cache=False,
        )
        eng_server = TestServer(create_engine_app(engine, owns_engine=False))
        await eng_server.start_server()
        gw = await GatewayHarness.create()
        try:
            gw.register_mock(f"http://127.0.0.1:{eng_server.port}",
                             [engine.model_id])
            headers = await gw.inference_headers()
            # warm every compiled shape outside the measured window: one
            # background-shaped stream (its prefill bucket + decode) and
            # one long prompt (256-chunk path, or budget-sized chunks)
            await stream_chat(gw, headers, BG_PROMPTS[0], priority="high",
                              max_tokens=8)
            await stream_chat(gw, headers, "x" * LONG_CHARS, priority="low",
                              max_tokens=2)

            marks: list[list[float]] = [[] for _ in BG_PROMPTS]
            bg = [
                asyncio.create_task(stream_chat(
                    gw, headers, prompt, priority="high",
                    max_tokens=160, marks=marks[i],
                ))
                for i, prompt in enumerate(BG_PROMPTS)
            ]
            ready_by = time.monotonic() + 120.0
            while any(len(m) < 3 for m in marks):  # all decoding for real
                if time.monotonic() > ready_by:
                    raise RuntimeError(
                        "background streams never reached steady decode"
                    )
                await asyncio.sleep(0.005)
            prefills_before = engine.core.metrics.prefill_step.n
            t_long = time.perf_counter()
            longs = await asyncio.gather(*(
                stream_chat(gw, headers, "x" * LONG_CHARS, priority="low",
                            max_tokens=4)
                for _ in range(3)
            ))
            long_wall = time.perf_counter() - t_long
            await asyncio.gather(*bg)
            prefill_steps = engine.core.metrics.prefill_step.n - prefills_before
            gaps = [b - a for m in marks for a, b in zip(m, m[1:])]
            return {
                "prefill_chunk_budget": budget,
                "background_streams": len(bg),
                "long_prompts": len(longs),
                "long_prompt_tokens_each": LONG_CHARS,
                "long_wall_s": round(long_wall, 2),
                "prefill_dispatches_for_longs": prefill_steps,
                "background_itl": _gap_stats(gaps),
                "gateway_goodput_by_priority":
                    gw.state.metrics.summary()["goodput_by_priority"],
            }
        finally:
            await gw.close()
            await eng_server.close()
            engine.shutdown()

    itl_off = await itl_mode(0)
    itl_on = await itl_mode(CHUNK_BUDGET)

    # ------------------------------------------------- (b) rate limiting
    async def ratelimit_phase() -> dict:
        engine = Engine.from_preset(
            "debug-tiny", model_id="bench-slo", num_slots=8,
            slot_capacity=128, prefill_buckets=(16, 32, 64),
            prefix_cache=False, seed=0,
        )
        eng_server = TestServer(create_engine_app(engine, owns_engine=False))
        await eng_server.start_server()
        gw = await GatewayHarness.create()
        try:
            gw.register_mock(f"http://127.0.0.1:{eng_server.port}",
                             [engine.model_id])
            greedy_key = await _make_named_key(gw, "greedy")
            bg_key = await _make_named_key(gw, "background")
            rps, burst = 2.0, 2.0
            gw.state.ratelimit = RateLimiter(RateLimitConfig(
                overrides={"greedy": {"rps": rps, "burst": burst,
                                      "tpm": 0.0}},
            ))

            def body(prio):
                return {"model": "bench-slo",
                        "messages": [{"role": "user", "content": "ping"}],
                        "max_tokens": 8, "temperature": 0.0,
                        "priority": prio}

            async def greedy_wave(n):
                resps = await asyncio.gather(*(
                    gw.client.post("/v1/chat/completions", json=body("low"),
                                   headers={"Authorization":
                                            f"Bearer {greedy_key}"})
                    for _ in range(n)
                ))
                out = []
                for r in resps:
                    retry_after = r.headers.get("Retry-After")
                    await r.release()
                    out.append((r.status, retry_after))
                return out

            async def background_trickle(n):
                ok = 0
                for _ in range(n):
                    r = await gw.client.post(
                        "/v1/chat/completions", json=body("high"),
                        headers={"Authorization": f"Bearer {bg_key}"})
                    ok += int(r.status == 200)
                    await r.release()
                    await asyncio.sleep(0.25)
                return ok

            # warm the engine shapes before the timed window
            await background_trickle(1)

            waves = max(4, requests // 6)
            t0 = time.perf_counter()
            bg_task = asyncio.create_task(background_trickle(8))
            greedy_results = []
            for _ in range(waves):
                greedy_results += await greedy_wave(6)
                await asyncio.sleep(0.4)
            bg_ok = await bg_task
            elapsed = time.perf_counter() - t0

            granted = [r for r in greedy_results if r[0] == 200]
            refused = [r for r in greedy_results if r[0] == 429]
            fair_share = burst + rps * elapsed
            summary = gw.state.metrics.summary()
            return {
                "greedy_limits": {"rps": rps, "burst": burst},
                "elapsed_s": round(elapsed, 2),
                "greedy_fired": len(greedy_results),
                "greedy_granted": len(granted),
                "greedy_429": len(refused),
                "greedy_fair_share_cap": round(fair_share, 1),
                "greedy_within_share": len(granted) <= fair_share + 1,
                "all_429_carry_retry_after": all(
                    ra is not None and int(ra) >= 1 for _, ra in refused
                ),
                "background_requests": 9,
                "background_ok": bg_ok + 1,  # incl. the warmup request
                "gateway_ratelimit_rejections":
                    summary["ratelimit_rejections_total"],
                "gateway_goodput_by_priority":
                    summary["goodput_by_priority"],
            }
        finally:
            await gw.close()
            await eng_server.close()
            engine.shutdown()

    ratelimit = await ratelimit_phase()

    # --------------------------------------- (c) preemption + resume
    async def preemption_phase() -> dict:
        engine = Engine.from_preset(
            "debug-tiny", model_id="bench-slo",
            num_slots=1, slot_capacity=128, prefill_buckets=(16, 32),
            kv_page_size=16, prefix_cache=False, seed=0,
        )
        eng_server = TestServer(create_engine_app(engine, owns_engine=False))
        await eng_server.start_server()
        gw = await GatewayHarness.create()
        try:
            gw.register_mock(f"http://127.0.0.1:{eng_server.port}",
                             [engine.model_id])
            headers = await gw.inference_headers()
            victim = "the quick brown fox jumps over"

            # uninterrupted reference (single slot, nothing else running)
            ref = await stream_chat(gw, headers, victim, priority="low",
                                    max_tokens=48)

            before = engine.core.metrics.preemptions_total
            marks: list[float] = []
            task = asyncio.create_task(stream_chat(
                gw, headers, victim, priority="low", max_tokens=48,
                marks=marks,
            ))
            ready_by = time.monotonic() + 120.0
            while len(marks) < 2:  # decoding, past first_pending
                if time.monotonic() > ready_by:
                    raise RuntimeError("victim stream never started decoding")
                await asyncio.sleep(0.005)
            hi = await stream_chat(gw, headers, "interloper",
                                   priority="high", max_tokens=6)
            got = await task
            m = engine.core.metrics
            return {
                "preemptions": m.preemptions_total - before,
                "resumes": m.preempt_resumes_total,
                "victim_tokens": len(got["text"]),
                "interloper_tokens": len(hi["text"]),
                "token_identical_resume": got["text"] == ref["text"],
                "engine_sched": engine.core.sched_info(),
            }
        finally:
            await gw.close()
            await eng_server.close()
            engine.shutdown()

    preempt = await preemption_phase()

    passed = (
        itl_on["background_itl"]["max_ms"]
        < itl_off["background_itl"]["max_ms"]
        and itl_on["prefill_dispatches_for_longs"]
        > itl_off["prefill_dispatches_for_longs"]
        and ratelimit["greedy_429"] > 0
        and ratelimit["greedy_within_share"]
        and ratelimit["all_429_carry_retry_after"]
        and ratelimit["background_ok"] == ratelimit["background_requests"]
        and preempt["preemptions"] >= 1
        and preempt["token_identical_resume"]
    )
    return {
        "metric": "slo_mix_workload",
        "passed": passed,
        "itl_bound": {"budget_off": itl_off, "budget_on": itl_on},
        "ratelimit": ratelimit,
        "preemption": preempt,
        "caveats": (
            "CPU host, debug-tiny model (512-position cap): the 'long' "
            "prompt is a 420-token stand-in for a 128k arrival and all "
            "wall-clock figures are CPU-bound; the mechanisms measured "
            "(chunk-budget interleaving, token-bucket shares, park/resume "
            "identity) transfer, the absolute latencies do not."
        ),
    }


async def run_disagg_bench(requests: int) -> dict:
    """Disaggregation workload (docs/disaggregation.md): the slo-mix ITL
    scenario — background streams decoding while 420-token prompts arrive —
    served three ways on the same traffic:

    (a) baseline  — `--role both`, chunk budget OFF (the prefill spike);
    (b) budget_on — `--role both`, chunk budget 16 (PR 10's overload
        protection: ITL bounded, prefill serialized against decode);
    (c) split     — `--role split` (PR 11): prefill pool + decode pool,
        page-id handoff, no budget.

    The claim under test: split holds background decode p99 ITL at or
    better than budget_on's (decode never waits behind more than one
    in-flight prefill dispatch) WITHOUT budget_on's prefill serialization
    penalty (long-prompt TTFT drops back toward the unbudgeted figure),
    and zero prefill dispatches execute on the decode pool's loop.
    Wall-clock numbers are CPU-host bound; the mechanism transfers.
    """
    from aiohttp.test_utils import TestServer

    from llmlb_tpu.engine.server import create_engine_app
    from llmlb_tpu.engine.service import Engine
    from tests.support import GatewayHarness

    LONG_CHARS = 420  # ByteTokenizer: ~1 token/char; slot capacity is 512
    CHUNK_BUDGET = 16
    BG_PROMPTS = (
        "background chat 0", "background chat 3",
        "lorem ipsum dolor sit amet", "alpha bravo charlie delta",
    )

    async def stream_chat(gw, headers, content, *, max_tokens,
                          marks: list | None = None) -> dict:
        payload = {
            "model": "bench-disagg",
            "messages": [{"role": "user", "content": content}],
            "max_tokens": max_tokens, "temperature": 0.0, "stream": True,
        }
        t0 = time.perf_counter()
        text, ttft = "", None
        resp = await gw.client.post("/v1/chat/completions", json=payload,
                                    headers=headers)
        assert resp.status == 200, await resp.text()
        async for raw in resp.content:
            line = raw.decode(errors="replace").strip()
            if not line.startswith("data: ") or line == "data: [DONE]":
                continue
            chunk = json.loads(line[len("data: "):])
            for c in chunk.get("choices", []):
                delta = c.get("delta", {}).get("content")
                if delta:
                    now = time.perf_counter()
                    if ttft is None:
                        ttft = now - t0
                    if marks is not None:
                        marks.append(now)
                    text += delta
        await resp.release()
        return {"text": text, "ttft_s": ttft}

    async def mode(label: str, *, role: str, budget: int) -> dict:
        extra = {"role": role}
        if role == "split":
            # 1 prefill slot + 7 decode slots: 4 background streams and 3
            # concurrent longs all fit the decode pool after adoption
            extra["disagg_prefill_slots"] = 1
        engine = Engine.from_preset(
            "debug-tiny", model_id="bench-disagg", num_slots=8,
            slot_capacity=512, prefill_buckets=(16, 32, 64, 128, 256),
            kv_page_size=16, seed=0,
            prefill_chunk_budget=budget, prefix_cache=False, **extra,
        )
        eng_server = TestServer(create_engine_app(engine, owns_engine=False))
        await eng_server.start_server()
        gw = await GatewayHarness.create()
        try:
            gw.register_mock(f"http://127.0.0.1:{eng_server.port}",
                             [engine.model_id])
            headers = await gw.inference_headers()
            # warm the compiled shapes outside the measured window
            await stream_chat(gw, headers, BG_PROMPTS[0], max_tokens=8)
            await stream_chat(gw, headers, "x" * LONG_CHARS, max_tokens=2)

            marks: list[list[float]] = [[] for _ in BG_PROMPTS]
            bg = [
                asyncio.create_task(stream_chat(
                    gw, headers, prompt, max_tokens=160, marks=marks[i],
                ))
                for i, prompt in enumerate(BG_PROMPTS)
            ]
            ready_by = time.monotonic() + 120.0
            while any(len(m) < 3 for m in marks):
                if time.monotonic() > ready_by:
                    raise RuntimeError(
                        "background streams never reached steady decode"
                    )
                await asyncio.sleep(0.005)
            t_long = time.perf_counter()
            longs = await asyncio.gather(*(
                stream_chat(gw, headers, "x" * LONG_CHARS, max_tokens=4)
                for _ in range(3)
            ))
            t_long_end = time.perf_counter()
            long_wall = t_long_end - t_long
            await asyncio.gather(*bg)
            gaps = [b - a for m in marks for a, b in zip(m, m[1:])]
            # the acceptance figure: inter-token gaps WHILE the long
            # prompts were in flight — the contention window the split
            # exists to protect. Whole-stream gaps are reported too, but
            # they dilute the prefill spike with minutes of uncontended
            # decode (and CPU-host noise swamps the p99 there).
            during = [
                b - a for m in marks for a, b in zip(m, m[1:])
                if b >= t_long and a <= t_long_end
            ]
            ttfts = sorted(r["ttft_s"] for r in longs)
            out = {
                "mode": label,
                "role": role,
                "prefill_chunk_budget": budget,
                "background_streams": len(bg),
                "long_prompts": len(longs),
                "long_prompt_tokens_each": LONG_CHARS,
                "long_wall_s": round(long_wall, 2),
                "long_ttft_s": {
                    "min": round(ttfts[0], 3),
                    "mean": round(sum(ttfts) / len(ttfts), 3),
                    "max": round(ttfts[-1], 3),
                },
                "background_itl": _gap_stats(gaps),
                "background_itl_during_prefill": _gap_stats(during),
            }
            if role == "split":
                out["prefill_dispatch_by_loop"] = dict(
                    engine.core.prefill_dispatch_by_loop
                )
                out["handoffs"] = dict(engine.core.metrics.handoff_total)
            return out
        finally:
            await gw.close()
            await eng_server.close()
            engine.shutdown()

    baseline = await mode("baseline", role="both", budget=0)
    budget_on = await mode("budget_on", role="both", budget=CHUNK_BUDGET)
    split = await mode("split", role="split", budget=0)

    passed = (
        # ITL during the contention window: split at or better than the
        # budget-bounded figure (the ISSUE acceptance criterion)
        split["background_itl_during_prefill"]["p99_ms"]
        <= budget_on["background_itl_during_prefill"]["p99_ms"]
        # TTFT: split does not pay the chunk serialization penalty —
        # long prompts land closer to the unbudgeted baseline than to
        # budget_on's serialized figure
        and split["long_ttft_s"]["mean"] < budget_on["long_ttft_s"]["mean"]
        # isolation invariant: the decode pool ran ZERO prefill dispatches
        and split["prefill_dispatch_by_loop"]["decode"] == 0
        and split["handoffs"]["in_process"] >= 7  # 4 bg + 3 longs
    )
    return {
        "metric": "disagg_workload",
        "passed": passed,
        "baseline": baseline,
        "budget_on": budget_on,
        "split": split,
        "caveats": (
            "CPU host, debug-tiny model (512-position cap): the 'long' "
            "prompt is a 420-token stand-in for a 128k arrival and all "
            "wall-clock figures are CPU-bound. The split-mode mechanism "
            "(two step loops, page-id handoff, decode-first turnstile) "
            "transfers to TPU; the absolute ITL/TTFT figures do not. "
            "Single host: both loops share one device, so split removes "
            "scheduling contention, not compute contention."
        ),
    }


async def run_kv_ship_bench(requests: int) -> dict:
    """KV shipping workload (docs/kv-cache.md): move KV, don't recompute it,
    measured where the recompute bill actually lands — a long context.

    Two scenarios on the real engine core (CPU backend, debug-tiny,
    seed 0, greedy), each run twice on identical traffic:

    (a) **preempt-resume**: a 384-token-context stream is parked
        mid-decode by a priority-0 interloper, then resumed. Replay mode
        (LLMLB_KV_OFFLOAD_BYTES=0) re-prefills prompt+committed; ship
        mode restores the parked pages from the host tier. Measured: the
        resume gap (interloper finish -> victim's next token), prefill
        dispatches, token identity across modes.
    (b) **warm return**: prompt A's cached prefix is evicted D2H under
        page pressure (an intervening same-size prompt B on a small
        pool), then A returns. Tier off re-prefills all 384 tokens (two
        chunk dispatches at this bucket set); tier on restores the
        aligned head H2D and prefills ONE suffix chunk. Measured: return
        TTFT, prefill dispatches on the return, token identity.

    Pass requires bit-identical outputs between modes in both scenarios,
    zero resume prefill dispatches in ship mode, the warm return landing
    in one suffix dispatch, and the ship-mode resume gap beating replay.
    """
    import numpy as np

    from llmlb_tpu.engine.presets import get_preset
    from llmlb_tpu.engine.scheduler import (
        EngineCore,
        Request,
        SamplingParams,
        event_tokens,
    )

    cfg = get_preset("debug-tiny")
    LONG = 384
    CORE_KW = dict(num_slots=1, slot_capacity=512,
                   prefill_buckets=(16, 32, 64, 128, 256), seed=0,
                   kv_page_size=16)
    iters = max(4, requests // 6)

    def _req(prompt, max_tokens=4, priority=1):
        return Request(prompt_ids=list(prompt),
                       sampling=SamplingParams(temperature=0.0,
                                               max_tokens=max_tokens,
                                               priority=priority))

    def _collect(request, timeout=300):
        toks = []
        while True:
            kind, value = request.events.get(timeout=timeout)
            if kind == "error":
                raise RuntimeError(f"engine error: {value}")
            if kind == "done":
                return toks
            toks.extend(event_tokens(kind, value))

    def _take(request, n=1, timeout=300):
        """Content events of a stream that is still generating, until
        they carry n tokens or more."""
        toks = []
        while len(toks) < n:
            kind, value = request.events.get(timeout=timeout)
            assert kind == "tokens", (kind, value)
            toks.extend(event_tokens(kind, value))
        return toks

    def _stats(xs: list[float]) -> dict:
        xs = sorted(xs)
        return {"mean_ms": round(1e3 * sum(xs) / len(xs), 2),
                "min_ms": round(1e3 * xs[0], 2),
                "max_ms": round(1e3 * xs[-1], 2)}

    def resume_scenario(ship: bool) -> dict:
        kw = dict(CORE_KW, prefix_cache=False)
        if ship:
            kw["kv_offload_bytes"] = 1 << 30
        core = EngineCore(cfg, **kw)
        core.start()
        try:
            rng = np.random.default_rng(17)
            prompt = list(rng.integers(1, cfg.vocab_size, size=(LONG,)))
            inter = [2] * 8
            # compile every shape outside the measured window — including
            # one full unmeasured park/resume so the restore scatter's jit
            # compile (ship mode) never lands inside a measured gap
            _collect(core.submit(_req(prompt, max_tokens=2, priority=2)))
            _collect(core.submit(_req(inter, max_tokens=4, priority=0)))
            warm = core.submit(_req(prompt, max_tokens=24, priority=2))
            _take(warm, 3)
            _collect(core.submit(_req(inter, max_tokens=4, priority=0)))
            _collect(warm)
            gaps, outs = [], []
            disp0 = sum(core.prefill_dispatch_by_loop.values())
            for _ in range(iters):
                victim = core.submit(_req(prompt, max_tokens=24,
                                          priority=2))
                toks = _take(victim, 3)  # decoding: the park is mid-stream
                _collect(core.submit(_req(inter, max_tokens=4, priority=0)))
                t0 = time.perf_counter()
                toks += _take(victim)
                gaps.append(time.perf_counter() - t0)
                outs.append(toks + _collect(victim))
            disp = sum(core.prefill_dispatch_by_loop.values()) - disp0
            info = core.kv_transfer_info()
            return {
                "mode": "ship" if ship else "replay",
                "parks": iters,
                "resume_gap": _stats(gaps),
                "prefill_dispatches": disp,
                "restored": info["restored_total"],
                "restored_bytes": info["restored_bytes_total"],
                "outputs": outs,
            }
        finally:
            core.stop()

    def warm_return_scenario(ship: bool) -> dict:
        kw = dict(CORE_KW, num_slots=2, kv_pages=40)
        if ship:
            kw["kv_offload_bytes"] = 1 << 30
        core = EngineCore(cfg, **kw)
        core.start()
        try:
            rng = np.random.default_rng(23)
            A = list(rng.integers(1, cfg.vocab_size, size=(LONG,)))
            B = list(rng.integers(1, cfg.vocab_size, size=(LONG,)))
            out_a = _collect(core.submit(_req(A, max_tokens=8)))
            _collect(core.submit(_req(B, max_tokens=8)))  # evicts A's prefix
            # unmeasured warm return: compiles the restore scatter (ship
            # mode) so the measured figure is the steady-state cost
            _collect(core.submit(_req(A, max_tokens=8)))
            _collect(core.submit(_req(B, max_tokens=8)))  # evicts A again
            disp0 = sum(core.prefill_dispatch_by_loop.values())
            t0 = time.perf_counter()
            req = core.submit(_req(A, max_tokens=8))
            first = _take(req)
            ttft = time.perf_counter() - t0
            out_a2 = first + _collect(req)
            info = core.kv_transfer_info()
            return {
                "mode": "ship" if ship else "replay",
                "return_ttft_ms": round(1e3 * ttft, 2),
                "return_prefill_dispatches":
                    sum(core.prefill_dispatch_by_loop.values()) - disp0,
                "tier_hits": info["offload"].get("hits", 0),
                "tier_spills": info["offload"].get("spills", 0),
                "outputs_identical": out_a2 == out_a,
            }
        finally:
            core.stop()

    replay = resume_scenario(False)
    ship = resume_scenario(True)
    warm_off = warm_return_scenario(False)
    warm_on = warm_return_scenario(True)
    resume_identical = ship.pop("outputs") == replay.pop("outputs")
    passed = (
        resume_identical
        # ship resumes ran ZERO prefill dispatches: the ledger shows only
        # each iteration's own chunked prefill + the interloper's
        and ship["prefill_dispatches"] < replay["prefill_dispatches"]
        and ship["restored"] >= iters
        and ship["resume_gap"]["mean_ms"] < replay["resume_gap"]["mean_ms"]
        and warm_on["outputs_identical"] and warm_off["outputs_identical"]
        and warm_on["tier_hits"] >= 1
        and warm_on["return_prefill_dispatches"] == 1  # one suffix chunk
        and warm_off["return_prefill_dispatches"] >= 2  # full re-prefill
    )
    return {
        "metric": "kv_ship_workload",
        "passed": passed,
        "context_tokens": LONG,
        "resume_outputs_token_identical": resume_identical,
        "preempt_resume": {"replay": replay, "ship": ship},
        "warm_return": {"replay": warm_off, "ship": warm_on},
        "caveats": (
            "CPU host, debug-tiny model: absolute gap/TTFT figures are "
            "CPU-bound and the D2H/H2D 'copies' are host memcpys — on a "
            "TPU the restore costs a real PCIe/ICI transfer but the "
            "replay costs a real O(context) prefill, so the structural "
            "figures (zero resume prefill dispatches, one-suffix-chunk "
            "warm returns, bit-identical outputs) are the transferable "
            "evidence; the wall-clock ratio is not."
        ),
    }


def _run_stub_server(port: int) -> None:
    """Hidden mode: a minimal OpenAI-compatible stub engine in its own
    process, so gateway workers under test never share a Python runtime
    (or GIL) with their upstream."""
    from aiohttp import web

    async def models(request):
        return web.json_response(
            {"object": "list",
             "data": [{"id": "bench-model", "object": "model"}]}
        )

    payload = {
        "id": "chatcmpl-stub", "object": "chat.completion",
        "model": "bench-model",
        "choices": [{"index": 0,
                     "message": {"role": "assistant", "content": "pong"},
                     "finish_reason": "stop"}],
        "usage": {"prompt_tokens": 7, "completion_tokens": 2,
                  "total_tokens": 9},
    }
    body = json.dumps(payload).encode()

    async def chat(request):
        await request.read()
        return web.Response(body=body, content_type="application/json")

    app = web.Application()
    app.router.add_get("/v1/models", models)
    app.router.add_post("/v1/chat/completions", chat)
    web.run_app(app, host="127.0.0.1", port=port, access_log=None,
                print=None)


def _run_client_runner(spec_json: str) -> None:
    """Hidden mode: one closed-loop load-generator process. Reads a JSON
    spec {url, api_key, seconds, concurrency}, hammers
    /v1/chat/completions, prints one JSON line {requests, errors,
    latencies_sample} (reservoir-sampled so the pipe stays bounded)."""
    import random

    import aiohttp

    spec = json.loads(spec_json)

    async def run() -> dict:
        rng = random.Random(1234)
        payload = {
            "model": "bench-model",
            "messages": [{"role": "user", "content": "ping"}],
            "stream": False,
        }
        headers = {"Authorization": f"Bearer {spec['api_key']}"}
        done = 0
        errors = 0
        sample: list[float] = []  # reservoir, cap 4000
        seen = 0
        deadline = time.perf_counter() + spec["seconds"]
        connector = aiohttp.TCPConnector(limit=0)
        async with aiohttp.ClientSession(connector=connector) as session:

            async def worker() -> None:
                nonlocal done, errors, seen
                while time.perf_counter() < deadline:
                    t0 = time.perf_counter()
                    try:
                        async with session.post(
                            spec["url"] + "/v1/chat/completions",
                            json=payload, headers=headers,
                        ) as resp:
                            await resp.read()
                            if resp.status == 200:
                                done += 1
                                lat = time.perf_counter() - t0
                                seen += 1
                                if len(sample) < 4000:
                                    sample.append(lat)
                                else:
                                    j = rng.randrange(seen)
                                    if j < 4000:
                                        sample[j] = lat
                            else:
                                errors += 1
                    except Exception:
                        errors += 1

            await asyncio.gather(
                *(worker() for _ in range(spec["concurrency"]))
            )
        return {"requests": done, "errors": errors,
                "latencies_sample": sample}

    print(json.dumps(asyncio.run(run())))


def _http_json(method: str, url: str, body=None, headers=None,
               timeout: float = 5.0):
    import urllib.request

    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, method=method,
                                 headers={"Content-Type": "application/json",
                                          **(headers or {})})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        raw = resp.read()
        return resp.status, (json.loads(raw) if raw else None)


def _free_port() -> int:
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _gateway_worker_pids(supervisor_pid: int) -> list[int]:
    """Direct children of the supervisor process (the forked workers)."""
    pids: list[int] = []
    try:
        for task in os.listdir(f"/proc/{supervisor_pid}/task"):
            path = f"/proc/{supervisor_pid}/task/{task}/children"
            try:
                with open(path) as f:
                    pids.extend(int(p) for p in f.read().split())
            except OSError:
                pass
    except OSError:
        pass
    return sorted(set(pids))


def _cpu_seconds(pids: list[int]) -> float:
    """Total utime+stime of the given pids, in seconds."""
    ticks = 0
    for pid in pids:
        try:
            with open(f"/proc/{pid}/stat") as f:
                fields = f.read().rsplit(")", 1)[1].split()
            ticks += int(fields[11]) + int(fields[12])  # utime, stime
        except (OSError, IndexError, ValueError):
            pass
    return ticks / os.sysconf("SC_CLK_TCK")


def run_throughput_bench(seconds: float, concurrency: int,
                         workers_list: list[int], clients: int) -> dict:
    """Closed-loop wrk-style load against REAL gateway processes
    (`serve --workers N`, SO_REUSEPORT) in front of stub engines, 1 vs N
    workers on the same host. Load generators and stubs are separate
    processes so neither shares a GIL with the gateway under test. Records
    the scaling curve with p50/p99 at matched offered load (same client
    pool for every N).

    Honesty: on a host with fewer cores than (workers + clients + stubs)
    the wall-clock curve measures the CONTAINER, not the gateway — Python
    workers scale with physical cores, and a 2-core CI box cannot show 4x
    anything. The bench therefore also records gateway CPU-time per
    request from /proc (core-count independent): flat CPU/request from 1
    to N workers means the multi-worker machinery (gossip, WAL sharing,
    SO_REUSEPORT) adds no per-request cost, i.e. near-linear scaling
    wherever cores exist. ``passed_3x_bar`` is only judged when the host
    has enough cores to make the wall-clock claim meaningful."""
    import shutil
    import signal as _signal
    import subprocess
    import tempfile

    tmp = tempfile.mkdtemp(prefix="llmlb-throughput-")
    procs: list = []
    results: dict[str, dict] = {}
    try:
        stub_ports = [_free_port(), _free_port()]
        for port in stub_ports:
            procs.append(subprocess.Popen(
                [sys.executable, __file__, "--stub-server", str(port)],
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
            ))
        for port in stub_ports:
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline:
                try:
                    status, _ = _http_json(
                        "GET", f"http://127.0.0.1:{port}/v1/models"
                    )
                    if status == 200:
                        break
                except OSError:
                    time.sleep(0.1)
            else:
                raise RuntimeError(f"stub on :{port} never came up")

        for n in workers_list:
            gw_port = _free_port()
            data_dir = os.path.join(tmp, f"gw{n}")
            env = dict(os.environ)
            env.update({
                "LLMLB_DATA_DIR": data_dir,
                "LLMLB_LOG_DIR": os.path.join(data_dir, "logs"),
                "LLMLB_ADMIN_PASSWORD": "benchpass1",
                # hot-path knobs the deployment docs recommend for load:
                # cached API-key auth, no per-request access log line
                "LLMLB_AUTH_CACHE_TTL": "60",
                "LLMLB_MAX_ACTIVE_PER_ENDPOINT": "4096",
                "LLMLB_HEALTH_CHECK_INTERVAL": "1",
                "LLMLB_TRACE_TIMELINE_SAMPLE": "0",
                # batched history writes for EVERY point on the curve (it is
                # the multi-worker default; the 1-worker baseline must not
                # pay sync WAL commits the N-worker runs skip)
                "LLMLB_HISTORY_FLUSH_SECS": "0.5",
            })
            base = f"http://127.0.0.1:{gw_port}"
            gw_log_path = os.path.join(tmp, f"gw{n}.log")
            gw_log = open(gw_log_path, "wb")
            gw = subprocess.Popen(
                [sys.executable, "-m", "llmlb_tpu.gateway.server", "serve",
                 "--host", "127.0.0.1", "--port", str(gw_port),
                 "--workers", str(n)],
                env=env, stdout=subprocess.DEVNULL, stderr=gw_log,
            )
            procs.append(gw)
            try:
                deadline = time.monotonic() + 60
                while time.monotonic() < deadline:
                    if gw.poll() is not None:
                        gw_log.flush()
                        with open(gw_log_path, errors="replace") as f:
                            tail = f.read()[-2000:]
                        raise RuntimeError(
                            f"gateway --workers {n} exited {gw.returncode}:"
                            f"\n{tail}"
                        )
                    try:
                        status, _ = _http_json("GET", f"{base}/health",
                                               timeout=1)
                        if status == 200:
                            break
                    except OSError:
                        time.sleep(0.2)
                else:
                    raise RuntimeError("gateway never answered /health")

                _, login = _http_json("POST", f"{base}/api/auth/login", {
                    "username": "admin", "password": "benchpass1",
                })
                admin = {"Authorization": f"Bearer {login['token']}"}
                _, key = _http_json("POST", f"{base}/api/api-keys", {
                    "name": "bench",
                    "permissions": ["openai.inference"],
                }, headers=admin)
                api_key = key["api_key"]
                for port in stub_ports:
                    _http_json("POST", f"{base}/api/endpoints", {
                        "base_url": f"http://127.0.0.1:{port}",
                        "name": f"stub-{port}",
                        "endpoint_type": "openai_compatible",
                    }, headers=admin)
                # model appears once the (primary worker's) health checker
                # probes + syncs; the registry change gossips to siblings
                deadline = time.monotonic() + 30
                while time.monotonic() < deadline:
                    try:
                        status, _ = _http_json(
                            "POST", f"{base}/v1/chat/completions",
                            {"model": "bench-model",
                             "messages": [{"role": "user",
                                           "content": "warm"}]},
                            headers={"Authorization": f"Bearer {api_key}"},
                        )
                        if status == 200:
                            break
                    except OSError:
                        pass
                    time.sleep(0.3)
                else:
                    raise RuntimeError("bench-model never became routable")

                worker_pids = _gateway_worker_pids(gw.pid) or [gw.pid]
                cpu_before = _cpu_seconds(worker_pids)
                spec = {"url": base, "api_key": api_key, "seconds": seconds,
                        "concurrency": max(1, concurrency // clients)}
                t0 = time.perf_counter()
                runners = [subprocess.Popen(
                    [sys.executable, __file__, "--client-runner",
                     json.dumps(spec)],
                    stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                ) for _ in range(clients)]
                rows = []
                for r in runners:
                    out, _ = r.communicate(timeout=seconds + 60)
                    rows.append(json.loads(out))
                elapsed = time.perf_counter() - t0
                gw_cpu_s = _cpu_seconds(worker_pids) - cpu_before

                requests_total = sum(r["requests"] for r in rows)
                errors = sum(r["errors"] for r in rows)
                lats = sorted(
                    x for r in rows for x in r["latencies_sample"]
                )

                def pct(p: float) -> float | None:
                    if not lats:
                        return None
                    return lats[min(len(lats) - 1, int(len(lats) * p))]

                # per-worker spread from the merged, worker-labeled /metrics
                per_worker: dict[str, float] = {}
                try:
                    import re as _re
                    import urllib.request as _ur

                    with _ur.urlopen(f"{base}/metrics", timeout=3) as resp:
                        for line in resp.read().decode().splitlines():
                            m = _re.match(
                                r'llmlb_gateway_requests_total\{.*'
                                r'route="/v1/chat/completions".*\} (\S+)',
                                line,
                            )
                            if m:
                                w = _re.search(r'worker="(\d+)"', line)
                                wk = w.group(1) if w else "0"
                                per_worker[wk] = (
                                    per_worker.get(wk, 0.0) + float(m.group(1))
                                )
                except OSError:
                    pass

                results[str(n)] = {
                    "workers": n,
                    "req_per_sec": round(requests_total / elapsed, 1),
                    "requests": requests_total,
                    "errors": errors,
                    "seconds": round(elapsed, 2),
                    "concurrency": clients * spec["concurrency"],
                    "client_processes": clients,
                    "p50_ms": (round(pct(0.50) * 1000, 2)
                               if lats else None),
                    "p90_ms": (round(pct(0.90) * 1000, 2)
                               if lats else None),
                    "p99_ms": (round(pct(0.99) * 1000, 2)
                               if lats else None),
                    "per_worker_requests": per_worker,
                    "gateway_cpu_seconds": round(gw_cpu_s, 2),
                    "gateway_cpu_ms_per_request": (
                        round(gw_cpu_s * 1000 / requests_total, 3)
                        if requests_total else None
                    ),
                    # capacity one dedicated core would sustain at this
                    # worker count's per-request cost — the figure that
                    # transfers to a host with enough cores
                    "implied_req_per_sec_per_gateway_core": (
                        round(1000.0 * requests_total / (gw_cpu_s * 1000), 1)
                        if gw_cpu_s > 0 and requests_total else None
                    ),
                }
                print(f"[bench] workers={n}: "
                      f"{results[str(n)]['req_per_sec']} req/s "
                      f"p50={results[str(n)]['p50_ms']}ms "
                      f"p99={results[str(n)]['p99_ms']}ms "
                      f"cpu/req={results[str(n)]['gateway_cpu_ms_per_request']}ms "
                      f"spread={per_worker}", file=sys.stderr)
            finally:
                if gw.poll() is None:
                    gw.send_signal(_signal.SIGTERM)
                    try:
                        gw.wait(timeout=15)
                    except subprocess.TimeoutExpired:
                        gw.kill()

        base_rps = results[str(workers_list[0])]["req_per_sec"]
        curve = {
            k: round(v["req_per_sec"] / base_rps, 2)
            for k, v in results.items()
        }
        host_cpus = os.cpu_count() or 1
        # a meaningful N-worker wall-clock claim needs cores for N workers
        # plus the load generators and stubs feeding them
        cores_needed = max(workers_list) + 2
        out = {
            "metric": "gateway_multiworker_throughput",
            "unit": "req/s",
            "workload": "closed-loop non-streaming chat vs stub engines",
            "host_cpus": host_cpus,
            "scaling_vs_1_worker": curve,
            "runs": results,
        }
        base_cpu = results[str(workers_list[0])].get(
            "gateway_cpu_ms_per_request"
        )
        top_cpu = results[str(max(workers_list))].get(
            "gateway_cpu_ms_per_request"
        )
        if base_cpu and top_cpu:
            # core-count-independent scaling evidence: per-request gateway
            # CPU must not grow with worker count (gossip/WAL overhead)
            out["cpu_per_request_ratio_Nv1"] = round(top_cpu / base_cpu, 2)
        if "4" in results and "1" in results:
            out["speedup_4_vs_1"] = round(
                results["4"]["req_per_sec"] / results["1"]["req_per_sec"], 2
            )
            if host_cpus >= cores_needed:
                out["passed_3x_bar"] = out["speedup_4_vs_1"] >= 3.0
            else:
                out["passed_3x_bar"] = None
                out["note"] = (
                    f"host has {host_cpus} cores; the 4-worker wall-clock "
                    f"bar needs >= {cores_needed} (workers + load "
                    "generators + stubs). Wall-clock curve recorded as "
                    "measured; cpu_per_request_ratio_Nv1 is the "
                    "core-independent scaling evidence on this host."
                )
        return out
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except Exception:
                p.kill()
        shutil.rmtree(tmp, ignore_errors=True)


async def run_chaos_multiworker(seconds: float, concurrency: int,
                                n_workers: int) -> dict:
    """Chaos drill across N shared-nothing worker states wired by the real
    gossip bus: one of two stub endpoints flaps (connect-refused at every
    worker's HTTP boundary, ~50% duty). Clients round-robin across the
    workers; the resilience layer + cross-worker breaker replication must
    hold >=99% client success, and the run measures the breaker
    propagation latency directly (trip on worker 0, time until every
    sibling denies)."""
    import tempfile

    from llmlb_tpu.gateway.app_state import build_app_state
    from llmlb_tpu.gateway.config import ServerConfig
    from llmlb_tpu.gateway.db import Database
    from llmlb_tpu.gateway.faults import FaultInjector, FaultRule
    from llmlb_tpu.gateway.resilience import BreakerState
    from llmlb_tpu.gateway.worker import WorkerInfo
    from tests.support import GatewayHarness, MockOpenAIEndpoint

    from aiohttp.test_utils import TestClient, TestServer
    from llmlb_tpu.gateway.app import create_app

    tmp = tempfile.mkdtemp(prefix="llmlb-chaos-mw-")
    os.environ["LLMLB_GOSSIP_DIR"] = os.path.join(tmp, "bus")
    # bench-tuned breaker/backoff knobs (same spirit as the single-worker
    # chaos run): several trip/half-open/close cycles within the window
    os.environ.update({
        "LLMLB_BREAKER_FAILURE_THRESHOLD": "3",
        "LLMLB_BREAKER_OPEN_SECS": "0.5",
        "LLMLB_BREAKER_OPEN_MAX_SECS": "2.0",
        "LLMLB_RETRY_BACKOFF_BASE": "0.005",
        "LLMLB_RETRY_BACKOFF_CAP": "0.05",
        "LLMLB_FAILOVER_QUEUE_TIMEOUT": "1.0",
        "LLMLB_ADMIN_PASSWORD": "adminpass1",
        "LLMLB_JWT_SECRET": "chaos-mw-secret",
        "LLMLB_AUTH_CACHE_TTL": "60",  # the multi-worker hot-path default
    })
    db_path = os.path.join(tmp, "gw.db")
    config = ServerConfig.from_env()
    config = config.__class__(**{**config.__dict__,
                                 "database_url": db_path})

    states = []
    harnesses: list[GatewayHarness] = []
    stable = await MockOpenAIEndpoint(model="chaos-model").start()
    flappy = await MockOpenAIEndpoint(model="chaos-model").start()
    try:
        for i in range(n_workers):
            state = await build_app_state(
                config, db=Database(db_path), start_background=False,
                worker=WorkerInfo(index=i, count=n_workers),
            )
            state.faults = FaultInjector()
            client = TestClient(TestServer(create_app(state)))
            await client.start_server()
            states.append(state)
            harnesses.append(GatewayHarness(state, client))
        gw0 = harnesses[0]
        gw0.register_mock(stable.url, ["chaos-model"], name="stable")
        ep_flappy = gw0.register_mock(flappy.url, ["chaos-model"],
                                      name="flappy")
        await asyncio.sleep(0.1)  # registry gossip -> sibling reloads
        for s in states[1:]:
            assert s.registry.get(ep_flappy.id) is not None, \
                "registry replication failed"
        headers = dict(await gw0.inference_headers())

        # --- direct propagation measurement (pre-traffic, clean clocks)
        threshold = states[0].resilience.config.breaker_failure_threshold
        t0 = time.perf_counter()
        for _ in range(threshold):
            states[0].resilience.record_failure(ep_flappy.id, "bench_trip")
        while any(s.resilience.allow(ep_flappy.id) for s in states[1:]):
            if time.perf_counter() - t0 > 2.0:
                break
            await asyncio.sleep(0.001)
        propagation_s = time.perf_counter() - t0
        propagated = not any(
            s.resilience.allow(ep_flappy.id) for s in states[1:]
        )
        for s in states:
            s.resilience.reset(ep_flappy.id)

        # --- chaos traffic across all workers
        ok = 0
        failed = 0
        statuses: dict[int, int] = {}
        deadline = time.perf_counter() + seconds
        running = True

        async def flapper() -> None:
            while running:
                rules = [s.faults.add_rule(FaultRule(
                    kind="connect_refused", endpoint="flappy", every_n=1,
                )) for s in states]
                await asyncio.sleep(0.7)
                for s, rule in zip(states, rules):
                    s.faults.remove_rule(rule)
                await asyncio.sleep(0.7)

        async def worker_task(i: int) -> None:
            nonlocal ok, failed
            n = 0
            client = harnesses[i % n_workers].client
            while time.perf_counter() < deadline:
                n += 1
                stream = (i + n) % 4 == 0
                payload = {
                    "model": "chaos-model",
                    "messages": [{"role": "user", "content": f"ping {n}"}],
                    "stream": stream,
                }
                try:
                    resp = await client.post(
                        "/v1/chat/completions", json=payload,
                        headers=headers,
                    )
                    body = await resp.read()
                    statuses[resp.status] = statuses.get(resp.status, 0) + 1
                    if resp.status == 200 and (
                        not stream or b"event: error" not in body
                    ):
                        ok += 1
                    else:
                        failed += 1
                except Exception:
                    failed += 1

        flap_task = asyncio.create_task(flapper())
        t0 = time.perf_counter()
        await asyncio.gather(*(worker_task(i) for i in range(concurrency)))
        elapsed = time.perf_counter() - t0
        running = False
        flap_task.cancel()
        try:
            await flap_task
        except asyncio.CancelledError:
            pass

        total = ok + failed
        success_rate = ok / max(1, total)
        trips = sum(
            1 for s in states
            if s.resilience.state_of(ep_flappy.id) != BreakerState.CLOSED
        )
        gossip_stats = [s.gossip.stats() for s in states
                        if s.gossip is not None]
        return {
            "metric": "chaos_multiworker_client_success_rate",
            "value": round(success_rate, 5),
            "unit": "fraction",
            "passed": success_rate >= 0.99 and propagated,
            "workers": n_workers,
            "requests": total,
            "ok": ok,
            "failed": failed,
            "statuses": statuses,
            "seconds": round(elapsed, 2),
            "req_per_sec": round(total / elapsed, 1),
            "breaker_propagation_ms": round(propagation_s * 1000, 2),
            "breaker_propagated_to_all_workers": propagated,
            "stub_requests": {"stable": len(stable.requests_seen),
                              "flappy": len(flappy.requests_seen)},
            "workers_with_tripped_breaker_at_end": trips,
            "gossip": {
                "sent_total": sum(g["sent_total"] for g in gossip_stats),
                "received_total": sum(
                    g["received_total"] for g in gossip_stats
                ),
                "mean_lag_ms": round(
                    sum(g["lag_s"] or 0.0 for g in gossip_stats)
                    / max(1, len(gossip_stats)) * 1000, 3
                ),
            },
        }
    finally:
        await stable.stop()
        await flappy.stop()
        for h in harnesses:
            await h.client.close()


async def run_chaos_bench(seconds: float, concurrency: int) -> dict:
    """Chaos drill: the real gateway + two stub endpoints serving one model,
    with one endpoint flapping hard (connect-refused injected at the proxy's
    HTTP boundary, ~50% duty cycle) for the whole run. Mixed non-streamed +
    streamed clients hammer /v1/chat/completions; the resilience layer
    (failover + breaker, docs/resilience.md) must keep the client-visible
    success rate >= 99%. Exit code 1 if it doesn't."""
    from llmlb_tpu.gateway.config import ResilienceConfig
    from llmlb_tpu.gateway.faults import FaultInjector, FaultRule
    from llmlb_tpu.gateway.resilience import ResilienceManager
    from tests.support import GatewayHarness, MockOpenAIEndpoint

    gw = await GatewayHarness.create()
    stable = await MockOpenAIEndpoint(model="chaos-model").start()
    flappy = await MockOpenAIEndpoint(model="chaos-model").start()
    try:
        gw.register_mock(stable.url, ["chaos-model"], name="stable")
        ep_flappy = gw.register_mock(flappy.url, ["chaos-model"],
                                     name="flappy")
        # Bench-tuned knobs: fast breaker cycles so several trip/half-open/
        # close rounds happen within a short run; tiny backoff so retries
        # don't dominate the latency figures.
        manager = ResilienceManager(
            ResilienceConfig(
                breaker_failure_threshold=3, breaker_open_s=0.5,
                breaker_open_max_s=2.0, backoff_base_s=0.005,
                backoff_cap_s=0.05, failover_queue_timeout_s=1.0,
            ),
            metrics=gw.state.metrics, events=gw.state.events,
            registry=gw.state.registry,
        )
        gw.state.resilience = manager
        gw.state.load_manager.resilience = manager
        faults = FaultInjector()
        gw.state.faults = faults

        headers = dict(await gw.inference_headers())

        ok = 0
        failed = 0
        stream_errors = 0
        statuses: dict[int, int] = {}
        deadline = time.perf_counter() + seconds
        running = True

        async def flapper() -> None:
            # ~50% duty cycle: dead 0.7 s, alive 0.7 s, forever
            while running:
                rule = faults.add_rule(FaultRule(
                    kind="connect_refused", endpoint="flappy", every_n=1,
                ))
                await asyncio.sleep(0.7)
                faults.remove_rule(rule)
                await asyncio.sleep(0.7)

        async def worker(i: int) -> None:
            nonlocal ok, failed, stream_errors
            n = 0
            while time.perf_counter() < deadline:
                n += 1
                stream = (i + n) % 4 == 0  # 1 in 4 requests streamed
                payload = {
                    "model": "chaos-model",
                    "messages": [{"role": "user", "content": f"ping {n}"}],
                    "stream": stream,
                }
                try:
                    resp = await gw.client.post(
                        "/v1/chat/completions", json=payload, headers=headers
                    )
                    body = await resp.read()
                    statuses[resp.status] = statuses.get(resp.status, 0) + 1
                    if resp.status == 200 and (
                        not stream or b"event: error" not in body
                    ):
                        ok += 1
                    else:
                        failed += 1
                        if resp.status == 200:
                            stream_errors += 1
                except Exception:
                    failed += 1

        flap_task = asyncio.create_task(flapper())
        t0 = time.perf_counter()
        await asyncio.gather(*(worker(i) for i in range(concurrency)))
        elapsed = time.perf_counter() - t0
        running = False
        flap_task.cancel()
        try:
            await flap_task
        except asyncio.CancelledError:
            pass

        # one source of truth: the same figures must appear in /metrics
        resp = await gw.client.get("/metrics")
        exposition = await resp.text()

        def series_sum(name: str) -> float:
            total = 0.0
            for line in exposition.splitlines():
                if line.startswith(name) and not line.startswith("# "):
                    total += float(line.rsplit(" ", 1)[1])
            return total

        total = ok + failed
        success_rate = ok / max(1, total)
        result = {
            "metric": "chaos_client_success_rate",
            "value": round(success_rate, 5),
            "unit": "fraction",
            "passed": success_rate >= 0.99,
            "requests": total,
            "ok": ok,
            "failed": failed,
            "stream_error_frames": stream_errors,
            "statuses": statuses,
            "seconds": round(elapsed, 2),
            "concurrency": concurrency,
            "req_per_sec": round(total / elapsed, 1),
            "stub_requests": {"stable": len(stable.requests_seen),
                              "flappy": len(flappy.requests_seen)},
            "flappy_breaker": manager.breaker_info(ep_flappy.id),
            "prometheus": {
                "failover_retries_total":
                    series_sum("llmlb_gateway_failover_retries_total"),
                "failover_recoveries_total":
                    series_sum("llmlb_gateway_failover_recoveries_total"),
                "breaker_transitions_total":
                    series_sum("llmlb_gateway_breaker_transitions_total"),
                "faults_injected_total":
                    series_sum("llmlb_gateway_faults_injected_total"),
                "retry_budget_exhausted_total":
                    series_sum("llmlb_gateway_retry_budget_exhausted_total"),
            },
        }
        return result
    finally:
        await stable.stop()
        await flappy.stop()
        await gw.close()


# ------------------------------------------------- engine-kill chaos drill


def _spawn_engine_process(port: int, *, extra_env: dict | None = None):
    """A REAL tpu:// engine server process (CPU backend, debug-tiny preset,
    seed-0 weights — every instance generates identical tokens), ready to
    be SIGKILLed/SIGTERMed like production."""
    import subprocess

    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "LLMLB_NATIVE_ROUTER": "0"})
    env.update(extra_env or {})
    return subprocess.Popen(
        [sys.executable, "-m", "llmlb_tpu.engine.server",
         "--preset", "debug-tiny", "--host", "127.0.0.1",
         "--port", str(port), "--num-slots", "16",
         "--slot-capacity", "2048", "--prefill-buckets", "16,32",
         "--kv-page-size", "16"],
        env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )


async def _wait_engine_up(session, port: int, timeout_s: float = 120.0):
    import aiohttp

    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        try:
            async with session.get(
                f"http://127.0.0.1:{port}/v1/models",
                timeout=aiohttp.ClientTimeout(total=2.0),
            ) as resp:
                if resp.status == 200:
                    return
        except Exception:
            pass
        await asyncio.sleep(0.25)
    raise RuntimeError(f"engine on port {port} never came up")


async def _merged_timeline_check(gw, rids, victim_pid) -> dict:
    """Durable-streams observability acceptance (docs/tracing.md): every
    resumed stream's `/api/traces/{id}?view=timeline` merge must carry
    flight-recorder events from BOTH engine processes — the killed
    victim's via the shared spool — in causal order, with the stream
    reaching a terminal event past the cut."""
    victim_src = f"engine-pid{victim_pid}"
    admin = await gw.admin_headers()
    out = {"victim_src": victim_src, "checked": 0, "resumed_verified": 0,
           "failures": []}
    for rid in rids:
        r = await gw.client.get(f"/api/traces/{rid}?view=timeline",
                                headers=admin)
        if r.status != 200:
            await r.release()
            continue
        body = await r.json()
        evs = (body.get("timeline") or {}).get("events") or []
        if not any(e.get("event") == "stream_resume" for e in evs):
            continue  # this stream was never cut
        out["checked"] += 1
        srcs = {e.get("src") for e in evs if e.get("src") != "gateway"}
        tss = [float(e.get("ts") or 0.0) for e in evs]
        victim_evs = [e for e in evs if e.get("src") == victim_src]
        after = [e for e in evs
                 if e.get("src") not in ("gateway", victim_src)]
        problems = []
        if not victim_evs:
            problems.append("no events from the killed engine")
        if len(srcs) < 2:
            problems.append("timeline is single-engine")
        if tss != sorted(tss):
            problems.append("timeline not monotone")
        if not any(e.get("event") in ("finished", "errored")
                   for e in after):
            problems.append("no terminal event past the cut")
        if victim_evs and after and (
                max(float(e.get("ts") or 0.0) for e in victim_evs)
                > min(float(e.get("ts") or 0.0) for e in after)):
            problems.append("survivor events precede the cut")
        if problems:
            out["failures"].append({"rid": rid, "problems": problems})
        else:
            out["resumed_verified"] += 1
    return out


async def run_chaos_engine_kill(streams: int = 8,
                                drills: tuple = ("kill", "drain")) -> dict:
    """The durable-streams chaos drill (docs/resilience.md): REAL engine
    processes behind the real gateway, N streams mid-generation, then

    - ``kill``: SIGKILL one engine — every cut stream must resume
      token-identically on the survivor and complete (>=99% client
      success, completed streams byte-equal to an undisturbed baseline);
    - ``drain``: SIGTERM one engine with a short LLMLB_DRAIN_GRACE_S —
      every in-flight stream either finishes inside the grace or is
      parked + cut for gateway-side resume; ZERO client-visible errors.

    Greedy and seeded-stochastic streams both run (token identity holds
    for both: seed-0 weights, per-request seeds folded by absolute
    position). Exit code 1 when any bar is missed.
    """
    import shutil
    import signal
    import tempfile

    from llmlb_tpu.gateway.config import ResilienceConfig
    from llmlb_tpu.gateway.faults import FaultInjector
    from llmlb_tpu.gateway.resilience import ResilienceManager
    from llmlb_tpu.gateway.types import EndpointStatus, EndpointType
    from tests.support import GatewayHarness, assert_sse_protocol

    t_start = time.monotonic()
    gw = await GatewayHarness.create()
    procs: list = []
    result: dict = {
        "metric": "chaos_engine_kill_drill",
        "unit": "fraction",
        "streams": streams,
        "drills": {},
    }

    # Shared flight-recorder spool: the SIGKILLed engine's lifecycle
    # events survive its death, so the survivor answers the victim's
    # timeline and /api/traces/{id}?view=timeline stays gap-free.
    flightrec_spool = tempfile.mkdtemp(prefix="llmlb-chaos-flightrec-")

    def spawn(extra_env=None):
        port = _free_port()
        env = {"LLMLB_FLIGHTREC_SPOOL": flightrec_spool}
        env.update(extra_env or {})
        proc = _spawn_engine_process(port, extra_env=env)
        procs.append(proc)
        return port, proc

    try:
        manager = ResilienceManager(
            ResilienceConfig(
                breaker_failure_threshold=3, breaker_open_s=0.5,
                breaker_open_max_s=2.0, backoff_base_s=0.005,
                backoff_cap_s=0.05, failover_queue_timeout_s=5.0,
                # the drill cuts ~all streams at once against near-zero
                # request volume, so the ratio term is 0 and the FLOOR is
                # the whole budget — size it for the drill (production
                # budgets scale with real traffic)
                retry_budget_min=4 * streams,
            ),
            metrics=gw.state.metrics, events=gw.state.events,
            registry=gw.state.registry,
        )
        gw.state.resilience = manager
        gw.state.load_manager.resilience = manager
        gw.state.faults = FaultInjector()
        headers = dict(await gw.inference_headers())
        headers["Content-Type"] = "application/json"

        port_a, proc_a = spawn()
        port_b, proc_b = spawn({"LLMLB_DRAIN_GRACE_S": "0.8"})
        await _wait_engine_up(gw.state.http, port_a)
        await _wait_engine_up(gw.state.http, port_b)
        ep_a = gw.register_mock(f"http://127.0.0.1:{port_a}", ["debug-tiny"],
                                endpoint_type=EndpointType.TPU, name="eng-a")
        ep_b = gw.register_mock(f"http://127.0.0.1:{port_b}", ["debug-tiny"],
                                endpoint_type=EndpointType.TPU, name="eng-b")

        contents: list[str] = [""] * streams

        def body_for(i: int, stream: bool, content: str | None = None,
                     max_tokens: int = 160) -> dict:
            body = {
                "model": "debug-tiny",
                "messages": [{"role": "user",
                              "content": content or contents[i]}],
                "max_tokens": max_tokens,
                "stream": stream,
            }
            if i % 2 == 0:
                body["temperature"] = 0.0  # greedy half
            else:
                body["temperature"] = 0.9
                body["seed"] = 1000 + i
            return body

        # ---- undisturbed baseline: non-streaming completions (the engine
        # collects the same stream internally, so text == stream text).
        # Prompt variants are probed for no-early-EOS (>=120 tokens) so the
        # kill reliably lands while streams are still decoding — the same
        # trick the PR 10 slo-mix bench uses.
        async def baseline(i: int) -> str:
            text = ""
            for j in range(12):
                content = f"chaos stream {i}.{j} lorem ipsum dolor"
                r = await gw.client.post(
                    "/v1/chat/completions",
                    json=body_for(i, stream=False, content=content),
                    headers=headers,
                )
                assert r.status == 200, await r.text()
                out = await r.json()
                text = out["choices"][0]["message"]["content"]
                contents[i] = content
                if out["usage"]["completion_tokens"] >= 120:
                    break
            return text

        baselines = list(await asyncio.gather(
            *(baseline(i) for i in range(streams))
        ))

        def stream_text(raw: bytes) -> str:
            parts = []
            for line in raw.split(b"\n"):
                line = line.strip()
                if not line.startswith(b"data:"):
                    continue
                data = line[len(b"data:"):].strip()
                if not data or data == b"[DONE]":
                    continue
                try:
                    obj = json.loads(data)
                except ValueError:
                    continue
                for choice in obj.get("choices") or []:
                    c = (choice.get("delta") or {}).get("content")
                    if isinstance(c, str):
                        parts.append(c)
            return "".join(parts)

        async def one_stream(i: int, first_byte_evt: asyncio.Event,
                             counter: list, rid: str) -> dict:
            out = {"ok": False, "identical": False, "error": None,
                   "rid": rid}
            try:
                r = await gw.client.post("/v1/chat/completions",
                                         json=body_for(i, stream=True),
                                         headers={**headers,
                                                  "X-Request-Id": rid})
                if r.status != 200:
                    out["error"] = f"http_{r.status}"
                    return out
                raw = b""
                async for chunk in r.content.iter_any():
                    raw += chunk
                    # a token, not the role chunk (`"content": ""`), which
                    # the engine writes before it submits the request: a
                    # kill there finds a stream the victim never recorded
                    if (not out.get("started")
                            and re.search(rb'"content": ?"[^"]', raw)):
                        out["started"] = True
                        counter[0] += 1
                        if counter[0] >= streams:
                            first_byte_evt.set()
                if b"event: error" in raw:
                    out["error"] = "error_frame"
                    return out
                assert_sse_protocol(raw, "openai")
                text = stream_text(raw)
                out["ok"] = True
                out["identical"] = text == baselines[i]
                if not out["identical"]:
                    out["error"] = "diverged"
                return out
            except Exception as e:
                out["error"] = f"{type(e).__name__}"
                return out

        engines = [{"proc": proc_a, "ep": ep_a, "alive": True},
                   {"proc": proc_b, "ep": ep_b, "alive": True}]

        async def drill(name: str, victim_sig) -> dict:
            # Reset the TPS EMAs so every live engine scores "unmeasured"
            # and the round-robin tie-break spreads the drill's streams
            # EVENLY — otherwise TPS scoring can concentrate every stream
            # on one endpoint and killing the other proves nothing.
            for e in engines:
                gw.state.load_manager.clear_tps_for_endpoint(e["ep"].id)
            evt = asyncio.Event()
            counter = [0]
            tasks = [
                asyncio.create_task(
                    one_stream(i, evt, counter, f"chaos-{name}-{i}"))
                for i in range(streams)
            ]
            await asyncio.wait_for(evt.wait(), timeout=60)
            victim = next(e for e in engines if e["alive"])
            victim["proc"].send_signal(victim_sig)
            victim["alive"] = False
            outs = await asyncio.gather(*tasks)
            victim["proc"].wait(timeout=30)
            ok = sum(1 for o in outs if o["ok"])
            identical = sum(1 for o in outs if o["identical"])
            return {
                "streams": streams,
                "victim": victim["ep"].name,
                "client_success": ok,
                "token_identical": identical,
                "success_rate": round(ok / streams, 4),
                "errors": [o["error"] for o in outs if o["error"]],
                "timeline": await _merged_timeline_check(
                    gw, [o["rid"] for o in outs if o["ok"]],
                    victim["proc"].pid),
            }

        summary0 = gw.state.metrics.summary()

        if "kill" in drills:
            # SIGKILL the busiest engine while every stream is
            # mid-generation: cut streams must resume on the survivor
            # token-identically
            result["drills"]["sigkill"] = await drill("sigkill",
                                                      signal.SIGKILL)
            # the victim is gone: take it out of the registry the way the
            # health checker eventually would, so the next drill is clean
            for e in engines:
                if not e["alive"]:
                    gw.state.registry.update_status(e["ep"].id,
                                                    EndpointStatus.OFFLINE)

        if "drain" in drills:
            # spawn a fresh peer so the drained engine has a resume target
            port_c, proc_c = spawn({"LLMLB_DRAIN_GRACE_S": "0.8"})
            await _wait_engine_up(gw.state.http, port_c)
            ep_c = gw.register_mock(f"http://127.0.0.1:{port_c}",
                                    ["debug-tiny"],
                                    endpoint_type=EndpointType.TPU,
                                    name="eng-c")
            engines.append({"proc": proc_c, "ep": ep_c, "alive": True})
            # warm the fresh engine (compiles) so the drill's streams are
            # placeable on it the moment the drain cuts them loose
            r = await gw.client.post(
                "/v1/chat/completions",
                json=body_for(0, stream=False,
                              content="warmup prompt", max_tokens=8),
                headers=headers,
            )
            await r.read()
            # SIGTERM the busiest engine (grace 0.8s): in-flight streams
            # finish inside the grace or are parked + cut for gateway-side
            # resume — zero client-visible errors either way
            result["drills"]["sigterm_drain"] = await drill(
                "sigterm_drain", signal.SIGTERM
            )

        summary1 = gw.state.metrics.summary()
        resumes1 = dict(summary1.get("stream_resumes") or {})
        resumes0 = dict(summary0.get("stream_resumes") or {})
        result["stream_resumes"] = {
            k: resumes1.get(k, 0) - resumes0.get(k, 0)
            for k in set(resumes1) | set(resumes0)
        }
        result["stream_resumed_tokens"] = (
            summary1.get("stream_resumed_tokens_total", 0)
            - summary0.get("stream_resumed_tokens_total", 0)
        )
        result["stream_interruptions"] = (
            summary1.get("stream_interruptions_total", 0)
            - summary0.get("stream_interruptions_total", 0)
        )

        bars = []
        for name, d in result["drills"].items():
            bars.append(d["success_rate"] >= 0.99)
            bars.append(d["token_identical"] == d["client_success"])
            # no resumed stream may show a broken merged timeline
            bars.append(not d["timeline"]["failures"])
        if "sigkill" in result["drills"]:
            # the SIGKILL acceptance: at least one resumed stream yields a
            # single merged timeline spanning both engine processes
            bars.append(
                result["drills"]["sigkill"]["timeline"]["resumed_verified"]
                >= 1)
        if "sigterm_drain" in result["drills"]:
            bars.append(not result["drills"]["sigterm_drain"]["errors"])
        # the drill is vacuous unless at least one stream actually resumed
        bars.append(result["stream_resumes"].get("success", 0) >= 1)
        result["value"] = min(
            d["success_rate"] for d in result["drills"].values()
        )
        result["passed"] = all(bars)
        result["seconds"] = round(time.monotonic() - t_start, 1)
        return result
    finally:
        for p in procs:
            try:
                p.kill()
                p.wait(timeout=10)
            except Exception:
                pass
        shutil.rmtree(flightrec_spool, ignore_errors=True)
        await gw.close()


def _openai_sse_text(body: bytes) -> str:
    """Concatenated delta content of an OpenAI chat SSE body."""
    parts = []
    for line in body.split(b"\n"):
        line = line.strip()
        if not line.startswith(b"data:"):
            continue
        data = line[len(b"data:"):].strip()
        if not data or data == b"[DONE]":
            continue
        try:
            obj = json.loads(data)
        except ValueError:
            continue
        for choice in obj.get("choices") or []:
            content = (choice.get("delta") or {}).get("content")
            if isinstance(content, str):
                parts.append(content)
    return "".join(parts)


async def run_rebalance_bench(streams: int = 12) -> dict:
    """Zero-downtime rebalancing drill (docs/resilience.md): two scenarios
    against the real gateway pump + mock resumable engines.

    - ``rolling_restart``: >= `streams` concurrent LIVE streams across
      three engines; each engine in turn advertises draining and the
      rebalancer evacuates it through park-export → resume while the
      clients keep reading. Bars: 100% client success, 100% token-identical
      output, zero terminal SSE error frames, every engine fully evacuated
      while draining.
    - ``hotspot``: background streams decode on a slow overloaded engine;
      a fast idle engine appears. Run twice — LLMLB_REBALANCE off
      (baseline: streams stay put) vs on (hot-spot directives migrate
      them) — and compare client-observed inter-chunk ITL p99. Bars:
      >= 1 hotspot/success migration, token identity in BOTH modes, and
      the rebalanced ITL p99 beating the pinned baseline.

    Exit code 1 when any bar is missed.
    """
    from llmlb_tpu.gateway.config import ResilienceConfig
    from llmlb_tpu.gateway.faults import FaultInjector
    from llmlb_tpu.gateway.rebalance import RebalanceConfig, Rebalancer
    from llmlb_tpu.gateway.resilience import ResilienceManager
    from llmlb_tpu.gateway.types import AcceleratorInfo, EndpointType
    from tests.support import GatewayHarness, MockResumableEndpoint

    t_start = time.monotonic()
    chat = "/v1/chat/completions"

    def wire_resilience(gw) -> None:
        manager = ResilienceManager(
            ResilienceConfig(backoff_base_s=0.005, backoff_cap_s=0.05,
                             failover_queue_timeout_s=2.0,
                             breaker_failure_threshold=3),
            metrics=gw.state.metrics, events=gw.state.events,
            registry=gw.state.registry,
        )
        gw.state.resilience = manager
        gw.state.load_manager.resilience = manager
        gw.state.faults = FaultInjector()

    async def one_stream(gw, headers, full_text) -> dict:
        body = {"model": "m", "stream": True,
                "messages": [{"role": "user", "content": "ping"}]}
        buf = bytearray()
        stamps: list[float] = []
        resp = await gw.client.post(chat, json=body, headers=headers)
        ok = resp.status == 200
        async for chunk in resp.content.iter_any():
            buf += chunk
            stamps.append(time.perf_counter())
        raw = bytes(buf)
        gaps = [b - a for a, b in zip(stamps, stamps[1:])]
        return {
            "ok": ok,
            "identical": _openai_sse_text(raw) == full_text,
            "error_frames": raw.count(b"event: error"),
            "gaps": gaps,
        }

    # ------------------------------------------------- (a) rolling restart
    script = list(range(100, 220))  # 120 tokens x 20 ms ≈ 2.4 s per stream
    full_text = "".join(MockResumableEndpoint.text_of(t) for t in script)
    gw = await GatewayHarness.create()
    mocks = []
    try:
        for i in range(3):
            mocks.append(await MockResumableEndpoint(
                model="m", script=script, inter_chunk_delay_s=0.02).start())
        eps = [gw.register_mock(m.url, ["m"], endpoint_type=EndpointType.TPU,
                                name=f"eng-{i}")
               for i, m in enumerate(mocks)]
        wire_resilience(gw)
        directory = gw.state.streams
        cfg = RebalanceConfig(max_concurrent=streams, per_minute=100000,
                              stream_window_s=0.05)
        # the directory enforces the per-stream window itself — give it the
        # drill's short window or a stream that already hopped once sits out
        # the default 60 s and the next drain can never finish
        directory.config = cfg
        reb = Rebalancer(
            gw.state.registry, gw.state.load_manager, directory,
            metrics=gw.state.metrics, config=cfg,
        )
        headers = dict(await gw.inference_headers())

        async def roll() -> dict:
            # wait until every stream is live, then restart engines in turn
            deadline = time.monotonic() + 5.0
            while (len(directory._streams) < streams
                   and time.monotonic() < deadline):
                await asyncio.sleep(0.01)
            peak_live = len(directory._streams)
            evacuated = []
            for ep in eps:
                ep.accelerator = AcceleratorInfo(accelerator="tpu",
                                                 draining=True)
                empty_by = time.monotonic() + 2.0
                while time.monotonic() < empty_by:
                    reb.tick()
                    await asyncio.sleep(0.05)
                    if directory.counts().get(ep.id, 0) == 0:
                        break
                evacuated.append(directory.counts().get(ep.id, 0) == 0)
                # "restart": the engine comes back clean and takes load again
                ep.accelerator = AcceleratorInfo(accelerator="tpu")
            return {"peak_live": peak_live, "evacuated": evacuated}

        roll_task = asyncio.create_task(roll())
        outs = await asyncio.gather(
            *(one_stream(gw, headers, full_text) for _ in range(streams)))
        rolled = await roll_task
        summary = gw.state.metrics.summary()
        rolling = {
            "streams": streams,
            "peak_concurrent_live": rolled["peak_live"],
            "client_success_rate": sum(o["ok"] for o in outs) / streams,
            "token_identical_rate": (
                sum(o["identical"] for o in outs) / streams),
            "error_frames": sum(o["error_frames"] for o in outs),
            "engines_fully_evacuated": sum(rolled["evacuated"]),
            "migrations": summary["rebalance_migrations"],
            "stream_resumes": summary["stream_resumes"],
        }
    finally:
        for m in mocks:
            await m.stop()
        await gw.close()

    # ------------------------------------------------------- (b) hot-spot
    script = list(range(100, 180))  # 80 tokens
    full_text = "".join(MockResumableEndpoint.text_of(t) for t in script)

    async def hotspot_mode(rebalance_on: bool) -> dict:
        gw = await GatewayHarness.create()
        hot = cold = None
        try:
            hot = await MockResumableEndpoint(
                model="m", script=script, inter_chunk_delay_s=0.05).start()
            ep_hot = gw.register_mock(hot.url, ["m"],
                                      endpoint_type=EndpointType.TPU,
                                      name="hot")
            wire_resilience(gw)
            headers = dict(await gw.inference_headers())
            n = max(4, streams // 2)
            tasks = [asyncio.create_task(one_stream(gw, headers, full_text))
                     for _ in range(n)]
            await asyncio.sleep(0.4)  # everyone decoding on the hot engine
            cold = await MockResumableEndpoint(
                model="m", script=script, inter_chunk_delay_s=0.01).start()
            ep_cold = gw.register_mock(cold.url, ["m"],
                                       endpoint_type=EndpointType.TPU,
                                       name="cold")
            ep_hot.accelerator = AcceleratorInfo(
                accelerator="tpu", num_slots=8, active_slots=8,
                queue_depth=4)
            ep_cold.accelerator = AcceleratorInfo(
                accelerator="tpu", num_slots=8)
            ticker = None
            if rebalance_on:
                reb = Rebalancer(
                    gw.state.registry, gw.state.load_manager,
                    gw.state.streams, metrics=gw.state.metrics,
                    config=RebalanceConfig(max_concurrent=n,
                                           per_minute=100000,
                                           stream_window_s=0.05),
                )

                async def tick_loop():
                    while True:
                        reb.tick()
                        await asyncio.sleep(0.05)

                ticker = asyncio.create_task(tick_loop())
            outs = await asyncio.gather(*tasks)
            if ticker is not None:
                ticker.cancel()
                try:
                    await ticker
                except asyncio.CancelledError:
                    pass
            # steady-state ITL: the last half of each stream's gaps — the
            # window where the planner has (or pointedly has not) acted;
            # whole-stream p99 would be dominated by the shared slow start
            gaps = [g for o in outs
                    for g in o["gaps"][len(o["gaps"]) // 2:]]
            summary = gw.state.metrics.summary()
            return {
                "streams": n,
                "client_success_rate": sum(o["ok"] for o in outs) / n,
                "token_identical_rate": sum(o["identical"] for o in outs) / n,
                "error_frames": sum(o["error_frames"] for o in outs),
                "itl": _gap_stats(gaps),
                "migrations": summary["rebalance_migrations"],
            }
        finally:
            for m in (hot, cold):
                if m is not None:
                    await m.stop()
            await gw.close()

    pinned = await hotspot_mode(False)
    rebalanced = await hotspot_mode(True)
    hotspot_migrations = sum(
        n for key, n in rebalanced["migrations"].items()
        if key == "hotspot/success")

    passed = (
        rolling["peak_concurrent_live"] >= streams
        and rolling["client_success_rate"] == 1.0
        and rolling["token_identical_rate"] == 1.0
        and rolling["error_frames"] == 0
        and rolling["engines_fully_evacuated"] == 3
        and rolling["migrations"].get("drain/success", 0) >= streams
        # migration is planning, not failure: nothing in stream_resumes
        and not rolling["stream_resumes"]
        and pinned["token_identical_rate"] == 1.0
        and rebalanced["token_identical_rate"] == 1.0
        and hotspot_migrations >= 1
        and rebalanced["itl"]["p99_ms"] < pinned["itl"]["p99_ms"]
    )
    return {
        "metric": "rebalance_zero_downtime_drill",
        "unit": "fraction",
        "value": rolling["client_success_rate"],
        "passed": passed,
        "rolling_restart": rolling,
        "hotspot": {"pinned": pinned, "rebalanced": rebalanced,
                    "itl_p99_improvement_ms": round(
                        pinned["itl"]["p99_ms"]
                        - rebalanced["itl"]["p99_ms"], 1)},
        "seconds": round(time.monotonic() - t_start, 1),
    }


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--concurrency", type=int, default=50)
    parser.add_argument(
        "--workload",
        choices=("proxy", "shared-prefix", "chaos",
                 "structured", "spec-decode", "quantized", "throughput",
                 "slo-mix", "disagg", "lora", "kv-ship", "fused",
                 "rebalance"),
        default="proxy",
    )
    parser.add_argument("--requests", type=int, default=24,
                        help="request count for --workload shared-prefix / "
                             "structured / spec-decode / "
                             "quantized")
    parser.add_argument("--engine-kill", action="store_true",
                        help="--workload chaos variant: spawn REAL engine "
                             "processes and SIGKILL one mid-stream (resume "
                             "drill) then SIGTERM-drain another "
                             "(docs/resilience.md durable streams)")
    parser.add_argument("--workers", type=int, default=None,
                        help="gateway worker processes: the top of the "
                             "scaling curve for --workload throughput "
                             "(default 4), or the in-process worker count "
                             "for --workload chaos (default 1)")
    parser.add_argument("--clients", type=int, default=4,
                        help="load-generator processes for --workload "
                             "throughput")
    # hidden child-process entry modes for --workload throughput
    parser.add_argument("--stub-server", type=int, default=None,
                        help=argparse.SUPPRESS)
    parser.add_argument("--client-runner", type=str, default=None,
                        help=argparse.SUPPRESS)
    args = parser.parse_args()
    if args.stub_server:
        _run_stub_server(args.stub_server)
        return
    if args.client_runner:
        _run_client_runner(args.client_runner)
        return
    if args.workload == "throughput":
        top = max(2, args.workers or 4)
        workers_list = sorted({1, 2, top} if top > 2 else {1, top})
        result = run_throughput_bench(
            args.seconds, args.concurrency, workers_list, args.clients
        )
        print(json.dumps(result))
        return
    if args.workload == "rebalance":
        result = asyncio.run(run_rebalance_bench(
            streams=max(12, args.requests // 2)))
        print(json.dumps(result))
        if not result["passed"]:
            sys.exit(1)
        return
    if args.workload not in ("proxy", "chaos"):
        _pin_platform()  # engine workloads touch jax: decide platform first
    if args.workload == "shared-prefix":
        result = asyncio.run(run_prefix_bench(args.requests))
    elif args.workload == "structured":
        result = asyncio.run(run_structured_bench(args.requests))
    elif args.workload == "spec-decode":
        result = asyncio.run(run_spec_bench(args.requests))
    elif args.workload == "slo-mix":
        result = asyncio.run(run_slo_mix_bench(args.requests))
        print(json.dumps(result))
        if not result["passed"]:
            sys.exit(1)
        return
    elif args.workload == "disagg":
        result = asyncio.run(run_disagg_bench(args.requests))
        print(json.dumps(result))
        if not result["passed"]:
            sys.exit(1)
        return
    elif args.workload == "lora":
        result = asyncio.run(run_lora_bench(args.requests))
        print(json.dumps(result))
        if not result["passed"]:
            sys.exit(1)
        return
    elif args.workload == "kv-ship":
        result = asyncio.run(run_kv_ship_bench(args.requests))
        print(json.dumps(result))
        if not result["passed"]:
            sys.exit(1)
        return
    elif args.workload == "fused":
        result = asyncio.run(run_fused_bench(args.requests))
        print(json.dumps(result))
        if not result["passed"]:
            sys.exit(1)
        return
    elif args.workload == "quantized":
        if args.requests < 40:
            # the peak-concurrency measurement needs enough requests to
            # saturate the int8 pool (~30 concurrent at the bench sizing)
            print(f"[bench] --requests {args.requests} raised to 40: the "
                  "quantized workload must saturate the page pool",
                  file=sys.stderr)
        result = asyncio.run(run_quantized_bench(max(args.requests, 40)))
    elif args.workload == "chaos":
        if args.engine_kill:
            result = asyncio.run(run_chaos_engine_kill(
                streams=max(8, min(16, args.requests // 2))
            ))
            print(json.dumps(result))
            if not result["passed"]:
                sys.exit(1)
            return
        if args.workers and args.workers > 1:
            result = asyncio.run(run_chaos_multiworker(
                args.seconds, min(args.concurrency, 16), args.workers
            ))
        else:
            result = asyncio.run(
                run_chaos_bench(args.seconds, min(args.concurrency, 16))
            )
        print(json.dumps(result))
        if not result["passed"]:
            sys.exit(1)
        return
    else:
        result = asyncio.run(run_bench(args.seconds, args.concurrency))
    print(json.dumps(result))


if __name__ == "__main__":
    sys.path.insert(0, ".")
    main()
