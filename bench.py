"""Headline bench: serving throughput through EngineCore (continuous batching).

Measures what BASELINE.md asks for — tokens/sec/chip on the 1B-class bench
model served through the engine's continuous-batching step loop (the same code
path /v1/chat/completions runs), plus TTFT p50 and an MFU estimate.

The bench runs on whatever backend JAX resolves and names it in the result.
It never substitutes one: with no accelerator it fails unless the caller set
JAX_PLATFORMS=cpu, and a CPU run is a tiny-config smoke whose metric name says
`cpu`. A failed run is a traceback and a non-zero exit. On success the output
is exactly ONE JSON line on stdout:

    {"metric": ..., "value": N, "unit": "tokens/sec/chip", "vs_baseline": N, ...}

All diagnostics go to stderr.
"""

from __future__ import annotations

import json
import os
import sys
import time

# Stand-in baseline: per-chip decode throughput of a 1B-class model on a
# vLLM/A100-class serving stack at batch ~32 (public figures cluster ~2-3k
# tok/s per accelerator for 1B models; we take the high end as the bar).
A100_CLASS_TOKS_PER_SEC = 3000.0


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def run_engine_bench(devices: list) -> dict:
    """Bench the continuous-batching engine loop on the resolved backend."""
    from llmlb_tpu.engine.scheduler import EngineCore, Request, SamplingParams
    from llmlb_tpu.engine.presets import get_preset

    platform = devices[0].platform
    on_tpu = platform == "tpu"
    if on_tpu:
        preset = "tinyllama-1.1b"
        num_slots, capacity = 32, 2048  # model max ctx; 4k prompts need 8k-ctx models
        buckets = (128, 256, 512)
        prompt_len, warm_tokens, max_tokens = 128, 16, 512
        measure_s = 10.0
        burst = int(os.environ.get("LLMLB_DECODE_BURST", "16"))
    else:
        preset = "debug-tiny"
        num_slots, capacity = 4, 128
        buckets = (16, 32)
        prompt_len, warm_tokens, max_tokens = 16, 4, 96
        measure_s = 3.0
        burst = 1

    cfg = get_preset(preset)
    n_chips = len(devices) if on_tpu else 1
    kind = devices[0].device_kind
    log(f"backend={platform} devices={n_chips} kind={kind}")

    t0 = time.perf_counter()
    core = EngineCore(
        cfg, num_slots=num_slots, slot_capacity=capacity,
        prefill_buckets=buckets, seed=0, decode_burst=burst,
    )
    core.start()
    log(f"engine up in {time.perf_counter() - t0:.1f}s "
        f"(slots={num_slots} cap={capacity})")

    import numpy as np

    rng = np.random.default_rng(0)

    def make_request(max_toks: int) -> Request:
        ids = list(rng.integers(1, cfg.vocab_size, size=(prompt_len,)))
        return Request(
            prompt_ids=ids,
            sampling=SamplingParams(temperature=0.7, top_p=0.95,
                                    max_tokens=max_toks),
        )

    def drain_until_done(reqs: list[Request], timeout: float) -> None:
        deadline = time.monotonic() + timeout
        for r in reqs:
            while time.monotonic() < deadline:
                kind_, _val = r.events.get(timeout=max(1.0, deadline - time.monotonic()))
                if kind_ in ("done", "error"):
                    break

    # ---- warmup: trigger every compile (prefill bucket + decode + sampling)
    t0 = time.perf_counter()
    warm = [make_request(warm_tokens) for _ in range(2)]
    for r in warm:
        core.submit(r)
    drain_until_done(warm, timeout=1200)
    log(f"warmup (compiles) in {time.perf_counter() - t0:.1f}s")

    # ---- measured run: fill all slots, sample steady-state throughput from
    # the engine's own token counter while every slot stays active.
    reqs = [make_request(max_tokens) for _ in range(num_slots)]
    submit_t = time.monotonic()
    for r in reqs:
        core.submit(r)

    while any(r.first_token_at is None for r in reqs):
        time.sleep(0.005)
        if time.monotonic() - submit_t > 1200:
            raise RuntimeError("requests never reached first token")
    ttfts = sorted((r.first_token_at - r.submitted_at) for r in reqs)
    ttft_p50_ms = 1000.0 * ttfts[len(ttfts) // 2]
    ttft_p99_ms = 1000.0 * ttfts[min(len(ttfts) - 1, int(len(ttfts) * 0.99))]

    stats0 = core.stats()
    t0 = time.monotonic()
    while True:
        time.sleep(0.25)
        s = core.stats()
        if s.active_slots < num_slots or time.monotonic() - t0 >= measure_s:
            break
    stats1 = core.stats()
    t1 = time.monotonic()
    window_tokens = stats1.total_tokens - stats0.total_tokens
    window_s = t1 - t0
    toks_per_sec = window_tokens / window_s

    drain_until_done(reqs, timeout=1200)

    # Long-context TTFT: one prompt far beyond the largest one-shot bucket
    # exercises the chunked-prefill path. Tiny on CPU; ~1.5k tokens (within
    # tinyllama's 2k ctx) on TPU.
    long_len = min(capacity - max(64, warm_tokens) - 2, 4096)
    long_ttft_ms = None
    if long_len > max(buckets):
        lr = make_request(16)
        lr.prompt_ids = list(rng.integers(1, cfg.vocab_size, size=(long_len,)))
        core.submit(lr)
        deadline = time.monotonic() + 1200
        while lr.first_token_at is None and time.monotonic() < deadline:
            time.sleep(0.005)
        if lr.first_token_at is not None:
            long_ttft_ms = 1000.0 * (lr.first_token_at - lr.submitted_at)
            log(f"long-prompt ({long_len} tokens) TTFT {long_ttft_ms:.0f}ms "
                f"(chunked prefill)")
        drain_until_done([lr], timeout=1200)

    core.stop()

    per_chip = toks_per_sec / max(n_chips, 1)

    # MFU: decode FLOPs/token ~= 2 * params, against the shared peak-spec
    # table (engine/telemetry.py CHIP_SPECS — the same figures the engine's
    # live llmlb_engine_mfu_ratio gauge divides by).
    from llmlb_tpu.engine.telemetry import chip_spec_for, model_flops_per_token

    n_params = sum(int(np.prod(v.shape)) for k, v in core.params.items()
                   if not k.endswith("_scale"))  # scales aren't parameters
    spec = chip_spec_for(kind)
    # weight-quantized engines are judged against the chip's int8 peak
    # (same column the live gauge divides by — telemetry.ChipSpec)
    peak = (spec.int8_flops if (spec and core.quant.weights)
            else (spec.peak_flops if spec else None))
    mfu = (model_flops_per_token(cfg, n_params) * per_chip / peak
           if (spec and on_tpu) else None)
    # the engine's own live figure over its recent decode window — should
    # track the bench's steady-state estimate on TPU
    engine_perf = core.perf_info()

    from llmlb_tpu.ops.attention import attention_mode

    kernels = attention_mode()
    # the engine resolves LLMLB_QUANTIZE itself; report what actually ran
    # next to the MFU estimate so a quantized number is never mistaken for
    # a bf16 one (int8 weights are judged against the int8 peak — the
    # engine's perf_info already picks the right column)
    quant_mode = core.quant.mode
    log(f"steady-state: {window_tokens} tokens / {window_s:.2f}s = "
        f"{toks_per_sec:.1f} tok/s ({per_chip:.1f}/chip), "
        f"ttft p50 {ttft_p50_ms:.1f}ms, kernels={kernels}, "
        f"mfu={mfu if mfu is not None else 'n/a'} quantize={quant_mode}")

    return {
        # a CPU run is a smoke of the code path, never a device's number:
        # its metric says so and it makes no comparison
        "metric": (f"engine_decode_tokens_per_sec_per_chip_{preset}" if on_tpu
                   else f"engine_decode_tokens_per_sec_{platform}_{preset}"),
        "value": round(per_chip, 2),
        "unit": "tokens/sec/chip" if on_tpu else f"tokens/sec ({platform})",
        "vs_baseline": (round(per_chip / A100_CLASS_TOKS_PER_SEC, 4)
                        if on_tpu else 0.0),
        "platform": platform,
        "device_kind": str(kind),
        "n_chips": n_chips,
        "model": preset,
        "batch_slots": num_slots,
        "decode_burst": burst,
        "ttft_p50_ms": round(ttft_p50_ms, 1),
        "ttft_p99_ms": round(ttft_p99_ms, 1),
        "long_prompt_tokens": long_len if long_ttft_ms is not None else None,
        "long_prompt_ttft_ms": (
            round(long_ttft_ms, 1) if long_ttft_ms is not None else None
        ),
        "mfu": round(mfu, 4) if mfu is not None else None,
        "quantize": quant_mode,
        "engine_mfu_live": engine_perf.get("mfu"),
        "engine_hbm_bw_utilization_live": engine_perf.get(
            "hbm_bw_utilization"
        ),
        "attention_kernels": kernels,
        "through_engine_core": True,
    }


def main() -> None:
    from llmlb_tpu.startup import configure_compile_cache, resolve_backend

    configure_compile_cache()
    print(json.dumps(run_engine_bench(resolve_backend())))


if __name__ == "__main__":
    main()
