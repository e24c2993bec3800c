"""The sample of a run and the per-request quantities read from it: shared
by the end-to-end metrics and the per-layer readers, so that both mean the
same requests."""

from __future__ import annotations

import re

from benchmark import stats


def request_ok(rec: dict) -> bool:
    """A counted response carried exactly the tokens asked for: as the
    engine's usage says, and as words the client received."""
    return (rec["status"] == 200 and not rec["error"]
            and rec["completion_tokens"] == rec["max_tokens"]
            and rec["words"] == rec["max_tokens"]
            and rec["first_s"] is not None)


def ok_sample(collected: dict) -> list[dict]:
    """The sampled requests that succeeded; a failed one misses every
    latency (it is counted under `failed`)."""
    return [r for r in collected["sample"] if request_ok(r)]


def ttfts(collected: dict) -> list[float]:
    """First content frame minus the instant the request was DUE."""
    return [r["first_s"] - r["due_s"] for r in ok_sample(collected)]


def tpots(collected: dict) -> list[float]:
    out = []
    for r in ok_sample(collected):
        v = stats.time_per_output_token(r["first_s"], r["last_s"], r["words"])
        if v is not None:
            out.append(v)
    return out


def norm_latencies(collected: dict) -> list[float]:
    """Last content frame minus the instant the request was DUE, over the
    output tokens: the whole of a request's wait, first token included,
    per token it asked for (the "normalized latency" of the Orca and vLLM
    papers)."""
    return [(r["last_s"] - r["due_s"]) / r["words"]
            for r in ok_sample(collected)]


def live_kv_tokens(collected: dict, a: float, b: float, points: int = 64
                   ) -> tuple[float, float]:
    """Time-averages over the client-clock interval [a, b] of (tokens of
    context alive in the engine, requests decoding), from the client's own
    records of every request: a request holds its prompt from its first
    token on, plus what it has generated so far, until its last token."""
    tokens = rows = 0.0
    live = [r for r in collected["requests"]
            if r["first_s"] is not None and r["last_s"] is not None
            and r["first_s"] <= b and r["last_s"] >= a]
    for i in range(points):
        t = a + (b - a) * (i + 0.5) / points
        for r in live:
            if not r["first_s"] <= t <= r["last_s"]:
                continue
            span = max(r["last_s"] - r["first_s"], 1e-9)
            tokens += r["prompt_tokens"] + r["words"] * (t - r["first_s"]) / span
            rows += 1
    return tokens / points, rows / points


def traced_interval(collected: dict) -> tuple[float, float]:
    """The traced part of the window on the client's clock: its last
    `trace_s` seconds (run.py starts the profiler there)."""
    s = float(collected["seconds"])
    return s - min(float(collected["settings"]["trace_s"]), s), s


_DTYPE_RE = re.compile(r"(pred|[suf]\d+|bf16|f8)")


def matching(table: dict, prefixes: list[str]) -> list[dict]:
    """Rows of a trace table (ops or modules) that belong to one of the named
    programs or kernels: the name itself, or the name followed by an instance
    or fingerprint (`.3`, `(1234)`) or by the result's type and shape
    (`_bf16_32_8_`, trace.op_label) — not a longer kernel's name."""
    out = []
    for k, v in table.items():
        for p in prefixes:
            rest = k[len(p):]
            if k.startswith(p) and (not rest or rest[0] in "(."
                                    or (rest[0] == "_"
                                        and _DTYPE_RE.match(rest[1:]))):
                out.append(v)
                break
    return out
