"""Model step: the least time one decode step of a dots3-note decoder could
take on this chip (`models/dots3_note.py`) — the weights it must read with
the held experts counted as the program's counter says they were touched,
the index keys of the cells the traced decode records say were scored, the
latents of the 2,048 a row they say were chosen, the ring cells in use and
the head, over the published bandwidth (or its operations over the
published peak, whichever is longer: benchmark/roofline/sparse_latent.py
`decode_step`) — as a share of `model.decode_step_s`: the share of the whole
step."""

from benchmark import manifest, peaks


def read(collected: dict):
    step_s = manifest.load_module("layer_metrics", "model.decode_step_s").read(collected)
    step = manifest.load_module("layer_metrics",
                                "kernel.sparse_index_select_roofline")
    recs = step.traced(collected)
    if step_s is None or not recs or not collected.get("peaks"):
        return None
    w = step.step_account(collected, recs)
    share, _bound = peaks.roofline_share_pct(w["flops"], w["bytes"], step_s,
                                             collected["peaks"])
    return share
