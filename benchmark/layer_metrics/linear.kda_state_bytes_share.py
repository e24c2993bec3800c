"""Model step, KDA layers: the state's part (read and written for the rows
the window's decode records say were advanced, `state_rows`, the
convolution's rows with it) of the bytes one decode step must move
(benchmark/roofline/kda.py `decode_step`, at the context the records say
was alive and the held experts they say were touched). It grows with the
rows and not with the context."""

from benchmark import manifest


def read(collected: dict):
    step = manifest.load_module("layer_metrics", "kernel.kda_step_roofline")
    recs = step.counted(collected)
    if not recs:
        return None
    w = step.step_account(collected, recs)
    return 100.0 * w["state_bytes"] / w["bytes"]
