"""Prefix cache: prompt tokens served from cached pages over prompt tokens
offered, in the window — the engine's `prefix_cached_tokens_total` counter
(/api/health), window delta, over the prompt tokens of the requests the
client sent inside the window."""

from benchmark import stats


def read(collected: dict):
    key = "prefix_cached_tokens_total"
    cached = (collected["health_end"]["metrics"][key]
              - collected["health_start"]["metrics"][key])
    offered = sum(r["prompt_tokens"] for r in collected["requests"]
                  if 0 <= r["send_s"] < collected["seconds"])
    return stats.share_pct(cached, offered)
