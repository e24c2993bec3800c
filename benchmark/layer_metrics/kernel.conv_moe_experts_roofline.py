"""Kernels: the grouped expert products' roofline share where a chip holds
all 64 three-matrix experts of 2048 x 1536 of every mixture layer
(`models/lfm2_moe.py`) — one matrix of every expert a layer touched and the
operations of its assignments a call (benchmark/roofline/conv_moe.py
`experts_call`; three calls a mixture layer: gate, up, down), over the
published peaks, as a share of the device time the trace gives the
grouped-matmul kernels. Bound by memory in decode.

Calls and time come from the SAME trace rows, and from the step records only
what a call moves on average (kernel.ssm_dense_step_roofline says why: a
trace that holds more or fewer steps than the records cannot move the
share). The records are those of the traced part of the window, prefills
among them: their products run under the same names, and a prefill's calls
touch more experts than a decode step's.

The readers of this configuration's other metrics take `counted`, `traced`,
`a_step` and `per_step` from here."""

from benchmark import manifest, moe_counters, peaks

ROOFLINE = "conv_moe"


def _own(collected: dict, recs: list[dict], kind: str | None) -> list[dict]:
    """Those of `recs` of `kind` (None: any) that a gated-short-convolution
    mixture left: they carry its counters. Nothing for any other
    configuration and nothing for a program that serves no such fields."""
    if "conv_L_cache" not in collected["config"]:
        return []
    return [r for r in recs if kind in (None, r["kind"])
            and "conv_rows" in r and "global_kv_tokens" in r]


def counted(collected: dict, kind: str | None = "decode") -> list[dict]:
    """The window's step records of `kind` with this family's counters."""
    return _own(collected, moe_counters.counted(collected, None), kind)


def traced(collected: dict, kind: str | None = "decode") -> list[dict]:
    """Those of `counted` whose middle lies in the traced part of the
    window."""
    return _own(collected, moe_counters.traced(collected), kind)


def a_step(collected: dict, recs: list[dict], field: str) -> float | None:
    """The mean of a counter over the MODEL steps of `recs` (a decode
    burst's record stands for its k steps, a prefill's for one)."""
    steps = sum(moe_counters.steps_of(r, collected) for r in recs)
    return sum(r[field] for r in recs) / steps if steps else None


def per_step(collected: dict, recs: list[dict]) -> dict | None:
    """What ONE model step of `recs` moved, as the program counted it:
    benchmark/roofline/conv_moe.py `decode_step`'s arguments."""
    if not recs:
        return None
    return {name: a_step(collected, recs, field) for name, field in (
        ("rows", "tokens"), ("conv_rows", "conv_rows"),
        ("live_cells", "global_kv_tokens"),
        ("experts_touched", "experts_touched"))}


def kernel_calls(collected: dict, ops: list[str]):
    """(calls, device seconds) of a kernel in the trace."""
    return manifest.load_module(
        "layer_metrics", "kernel.ssm_dense_step_roofline").kernel_calls(
            collected, ops)


def read(collected: dict):
    roofline = manifest.load_module("roofline", ROOFLINE)
    recs = traced(collected, None)
    calls, seconds = kernel_calls(collected, roofline.EXPERT_OPS)
    if not recs or not calls or not collected.get("peaks"):
        return None
    a_layer = roofline.moe_layers(collected["config"])
    w = roofline.experts_call(
        collected["config"],
        experts_touched=calls * a_step(collected, recs, "experts_touched")
        / a_layer,
        assignments=calls * a_step(collected, recs, "expert_assignments")
        / a_layer)
    share, _bound = peaks.roofline_share_pct(w["flops"], w["bytes"], seconds,
                                             collected["peaks"])
    return share
