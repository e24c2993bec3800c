"""Kernels: the KDA step kernel's roofline share (`kda_step`:
ops/delta_rule.py's step with a decay a key channel) — the state of the rows
the traced decode records say were advanced (`state_rows`, a KDA layer's
state read and written for each, its inputs beside it:
benchmark/roofline/kda.py) over the published peaks, as a share of the
device time the trace gives the kernel. The kernel walks every slot, so rows
that do not decode cost it time and count for nothing here. Also what this
family's other readers share: its step records and their account."""

from benchmark import manifest, peaks, samples


def counted(collected: dict) -> list[dict]:
    """The window's decode records of a Kimi-Linear program: those that
    carry its counters. Nothing for any other program or configuration."""
    roofline = manifest.load_module("roofline", "kda")
    if not roofline.is_kda(collected["config"]):
        return []
    return [r for r in collected.get("steps") or []
            if r["kind"] == "decode" and "state_rows" in r
            and "global_kv_tokens" in r and "experts_touched" in r]


def traced(collected: dict) -> list[dict]:
    """Those of `counted` whose middle lies in the traced part of the window
    (the trace's wall-clock start and stop)."""
    tr = collected.get("trace") or {}
    if "wall_start" not in tr or "wall_stop" not in tr:
        return []
    return [r for r in counted(collected)
            if tr["wall_start"] <= r["ts"] - r["total_s"] / 2 <= tr["wall_stop"]]


def steps_of(rec: dict) -> int:
    """Model steps a decode record stands for: its burst's k."""
    return max(1, rec["tokens"] // max(1, rec["active_slots"]))


def step_account(collected: dict, recs: list[dict]) -> dict:
    """roofline/kda.py's `decode_step` at the rows a step of `recs`
    advanced, the context it kept alive and the held experts it touched,
    all as the program counted them."""
    roofline = manifest.load_module("roofline", "kda")
    hf = collected["config"]
    steps = sum(steps_of(r) for r in recs)
    return roofline.decode_step(
        hf, collected["engine"],
        live_tokens=sum(r["global_kv_tokens"] for r in recs)
        / (roofline.latent_layers(hf) * steps),
        rows=sum(r["state_rows"] for r in recs) / steps,
        experts_touched=sum(r["experts_touched"] for r in recs) / steps)


def read(collected: dict):
    roofline = manifest.load_module("roofline", "kda")
    tr = collected.get("trace") or {}
    rows = samples.matching(tr.get("ops") or {}, roofline.STEP_OPS)
    recs = traced(collected)
    if not rows or not recs or not collected.get("peaks"):
        return None
    hf = collected["config"]
    seconds = sum(r["time_s"] for r in rows)
    w = roofline.step_call(hf, rows=sum(r["state_rows"] for r in recs)
                           * roofline.kda_layers(hf))
    share, _bound = peaks.roofline_share_pct(w["flops"], w["bytes"], seconds,
                                             collected["peaks"])
    return share
