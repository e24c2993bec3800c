"""Kernels: the learned indexer's roofline share where a query attends over
the 2,048 cells its indexer scores highest (`models/dots3_note.py`) — the
index keys of the cells the traced decode records say were scored
(`index_scored_cells`: a live row's whole length in every full layer), each
read once for all 64 index heads, and the score a cell the top-k's search
reads (benchmark/roofline/sparse_latent.py `index_select`), over the
published peaks, as a share of the device time the trace gives the score
kernel (`index_scores_decode`). The kernel scores every page of the step's
context bucket, whatever of it is live, and the search for the 2,048th score
runs in unnamed fusions beside it whose time is not in the share: the share
is the score kernel's alone. Also what this family's other readers share:
its step records and their account."""

from benchmark import manifest, peaks, samples


def counted(collected: dict) -> list[dict]:
    """The window's decode records of a dots3-note program: those that
    carry its counters. Nothing for any other program or configuration."""
    roofline = manifest.load_module("roofline", "sparse_latent")
    if not roofline.is_sparse(collected["config"]):
        return []
    return [r for r in collected.get("steps") or []
            if r["kind"] == "decode" and "index_scored_cells" in r
            and "index_selected_cells" in r and "window_kv_tokens" in r]


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
    """roofline/sparse_latent.py's `decode_step` at the cells a step of
    `recs` scored, chose and held in its rings, the rows it advanced and the
    held experts it touched, all as the program counted them."""
    roofline = manifest.load_module("roofline", "sparse_latent")
    steps = sum(steps_of(r) for r in recs)

    def a_step(name):
        return sum(r.get(name, 0) for r in recs) / steps

    return roofline.decode_step(
        collected["config"], collected["engine"],
        scored_cells=a_step("index_scored_cells"),
        selected_cells=a_step("index_selected_cells"),
        window_cells=a_step("window_kv_tokens"), rows=a_step("tokens"),
        experts_touched=a_step("experts_touched"))


def kernel_share(collected: dict, ops: str, account: str, cells: str,
                 kind: str):
    """The roofline share of the kernel `ops` names, its work `account` at
    the `cells` the traced decode records counted over the rows they
    advanced in the layers of `kind`."""
    roofline = manifest.load_module("roofline", "sparse_latent")
    tr = collected.get("trace") or {}
    rows = samples.matching(tr.get("ops") or {}, getattr(roofline, ops))
    recs = traced(collected)
    if not rows or not recs or not collected.get("peaks"):
        return None
    hf = collected["config"]
    w = getattr(roofline, account)(
        hf, cells=sum(r[cells] for r in recs),
        rows=sum(r["tokens"] for r in recs) * roofline.layers(
            hf, getattr(roofline, kind)))
    share, _bound = peaks.roofline_share_pct(
        w["flops"], w["bytes"], sum(r["time_s"] for r in rows),
        collected["peaks"])
    return share


def read(collected: dict):
    return kernel_share(collected, "INDEX_OPS", "index_select",
                        "index_scored_cells", "FULL")
