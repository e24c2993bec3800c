"""Kernels: the `paged_latent_decode` kernel's roofline share where seven
latent-attention layers WITHOUT rotary stand beside twenty KDA layers
(`models/kimi_linear.py`) — the latents and shared keys the traced decode
records say were alive (`global_kv_tokens`: a live row's whole length in
every latent layer), each read once for all 32 heads
(benchmark/roofline/kda.py `latent_decode`), over the published peaks, as a
share of the device time the trace gives the kernel."""

from benchmark import manifest, peaks, samples


def read(collected: dict):
    roofline = manifest.load_module("roofline", "kda")
    step = manifest.load_module("layer_metrics", "kernel.kda_step_roofline")
    tr = collected.get("trace") or {}
    rows = samples.matching(tr.get("ops") or {}, roofline.LATENT_DECODE_OPS)
    recs = step.traced(collected)
    if not rows or not recs or not collected.get("peaks"):
        return None
    hf = collected["config"]
    w = roofline.latent_decode(
        hf, cells=sum(r["global_kv_tokens"] for r in recs),
        rows=sum(r["state_rows"] for r in recs) * roofline.latent_layers(hf))
    share, _bound = peaks.roofline_share_pct(
        w["flops"], w["bytes"], sum(r["time_s"] for r in rows),
        collected["peaks"])
    return share
