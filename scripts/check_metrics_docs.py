#!/usr/bin/env python
"""Cross-check exported metric names against docs/monitoring/README.md —
and the monitoring ASSETS against the exporters.

Two directions, both wired as tier-1 tests (tests/test_metrics_docs.py);
also runnable standalone:

    python scripts/check_metrics_docs.py

1. Every Prometheus series the engine and gateway registries can emit must
   be named VERBATIM somewhere in docs/monitoring/README.md — new gauges
   (like the page-pool family) cannot ship undocumented. Enumeration is by
   rendering the real registries (with every optional block enabled and one
   sample recorded per labeled family, so conditional series render too)
   plus the scrape-time gauge/counter literals the gateway /metrics handler
   injects (regex over llmlb_tpu/gateway/app.py — they live in a dict at
   the call site, not in the registry).

2. Every llmlb_* series referenced by docs/monitoring/grafana-tpu-engine.json
   and prometheus-alerts.yml must exist in the exportable set, so dashboards
   and alert rules cannot drift from the exporters (a renamed gauge breaks
   the build, not the on-call's 3am debugging session).
"""

from __future__ import annotations

import re
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
DOCS = REPO / "docs" / "monitoring" / "README.md"
GRAFANA = REPO / "docs" / "monitoring" / "grafana-tpu-engine.json"
ALERTS = REPO / "docs" / "monitoring" / "prometheus-alerts.yml"

_TYPE_RE = re.compile(r"^# TYPE (\S+) ", re.MULTILINE)
_GATEWAY_LITERAL_RE = re.compile(r'"(llmlb_gateway_[a-z0-9_]+)"')
# two segments minimum after the prefix: skips prose like "llmlb_gateway_*"
# and module paths like "llmlb_tpu/gateway" in asset comments
_SERIES_RE = re.compile(r"\b(llmlb_[a-z0-9]+(?:_[a-z0-9]+)+)\b")
_CLOUD_LITERAL_RE = re.compile(r"(llmlb_cloud_[a-z0-9_]+)")
# histogram exposition suffixes resolve to their family name
_HIST_SUFFIX_RE = re.compile(r"_(bucket|sum|count)$")


def engine_metric_names() -> set[str]:
    from llmlb_tpu.engine.metrics import EngineMetrics

    m = EngineMetrics()
    # one sample per labeled lora family so the conditional series render
    m.record_lora_request("sample")
    m.record_lora_load(0.0)
    text = m.render(
        queue_depth=0, active_slots=0, num_slots=1,
        prefix_cache={
            "enabled": True, "entries": 0,
            "pinned_pages": 0, "pinned_hbm_bytes": 0,
        },
        structured={
            "enabled": True, "mask_cache_entries": 0, "mask_cache_bytes": 0,
        },
        kv_cache={
            "layout": "paged", "page_size": 128, "pages_total": 0,
            "pages_free": 0, "pages_active": 0, "pages_pinned": 0,
            "utilization": 0.0, "fragmentation": 0.0,
            "waste_tokens_mean": 0.0, "bytes_per_page": 0, "hbm_bytes": 0,
            "bytes_per_token": 0,
            "kv_dtype": "int8",
        },
        perf={
            "available": True, "mfu": 0.0, "hbm_bw_utilization": 0.0,
            "flops_per_token": 0.0, "bytes_per_token": 0.0,
        },
        quant={"mode": "all", "param_bytes": 0},
        sched={"queued_by_class": {"high": 0, "normal": 0, "low": 0},
               "queued_by_role": {"prefill": 0, "decode": 0}},
        lora={"enabled": True, "resident": ["sample"],
              "available": ["sample"], "max_adapters": 8},
        flightrec={"enabled": True, "events_total": 0,
                   "events_dropped_total": 0, "requests_tracked": 0,
                   "queue_seconds_total": 0.0, "service_seconds_total": 0.0},
        kv_offload={"enabled": True, "budget_bytes": 0, "bytes": 0,
                    "entries": 0, "prefix_entries": 0, "parked_entries": 0,
                    "hits": 0, "misses": 0, "spills": 0, "evictions": 0,
                    "spilled_bytes": 0, "restored_bytes": 0},
    )
    return set(_TYPE_RE.findall(text))


def gateway_metric_names() -> set[str]:
    from llmlb_tpu.gateway.config import SloConfig
    from llmlb_tpu.gateway.metrics import GatewayMetrics

    g = GatewayMetrics(slo=SloConfig())
    # one sample per labeled family so every series renders
    g.record_request("/v1/chat/completions", 500)
    g.record_retry("chat")
    g.record_queue_timeout("m")
    g.record_ttft("m", "e", 0.1)
    g.record_e2e("m", "e", 0.1)
    g.record_queue_wait("m", "e", 0.1)
    # resilience families (gateway/resilience.py)
    g.record_failover_retry("m", "connect_error")
    g.record_failover_recovery("m")
    g.record_retry_budget_exhausted()
    g.record_breaker_transition("e", "open")
    g.set_breaker_state("e", 2)
    g.record_stream_interruption("m", "e")
    g.record_fault_injected("connect_refused")
    g.record_structured_request("json_schema")
    g.record_structured_rejected()
    g.record_slo("m", 0.01, 0.01)  # SLO goodput family
    names = set(_TYPE_RE.findall(g.render()))
    # scrape-time gauges/counters injected by the /metrics handler — the
    # exposition builder lives in app_state.gateway_exposition (shared by
    # the handler and the multi-worker metrics spool), with app.py kept in
    # the scan for anything still injected at the route
    for module in ("app.py", "app_state.py"):
        src = (REPO / "llmlb_tpu" / "gateway" / module).read_text()
        names |= set(_GATEWAY_LITERAL_RE.findall(src))
    return names


def cloud_metric_names() -> set[str]:
    """llmlb_cloud_* series from the cloud-proxy exposition builder (string
    literals in api_cloud.py; suffixed bucket/sum/count lines resolve to
    their histogram family)."""
    src = (REPO / "llmlb_tpu" / "gateway" / "api_cloud.py").read_text()
    return {
        _HIST_SUFFIX_RE.sub("", n) for n in _CLOUD_LITERAL_RE.findall(src)
    }


def exportable_names() -> set[str]:
    return (engine_metric_names() | gateway_metric_names()
            | cloud_metric_names())


def referenced_series(*paths: Path) -> set[str]:
    """Every llmlb_* series named in the monitoring assets (dashboard
    exprs, alert exprs), suffix-normalized to family names."""
    names: set[str] = set()
    for path in paths:
        for n in _SERIES_RE.findall(path.read_text()):
            names.add(_HIST_SUFFIX_RE.sub("", n))
    return names


def undocumented(names: set[str], docs_text: str) -> list[str]:
    return sorted(n for n in names if n not in docs_text)


def unknown_references(referenced: set[str],
                       exportable: set[str]) -> list[str]:
    return sorted(n for n in referenced if n not in exportable)


def main() -> int:
    docs_text = DOCS.read_text()
    rc = 0
    missing = undocumented(exportable_names(), docs_text)
    if missing:
        print("metric names exported but not documented in "
              f"{DOCS.relative_to(REPO)}:", file=sys.stderr)
        for name in missing:
            print(f"  - {name}", file=sys.stderr)
        rc = 1
    dangling = unknown_references(referenced_series(GRAFANA, ALERTS),
                                  exportable_names())
    if dangling:
        print("series referenced by dashboards/alerts but exported by "
              "nothing:", file=sys.stderr)
        for name in dangling:
            print(f"  - {name}", file=sys.stderr)
        rc = 1
    if rc == 0:
        print("all exported metric names are documented and every "
              "dashboard/alert series exists")
    return rc


if __name__ == "__main__":
    sys.path.insert(0, str(REPO))
    raise SystemExit(main())
