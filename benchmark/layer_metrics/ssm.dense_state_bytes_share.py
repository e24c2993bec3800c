"""Model step, state-space layers of a dense hybrid: the recurrent state's
part (read and written for the rows the window's decode records say were
advanced, `state_rows`, the convolution's rows with it) of the bytes one
decode step must move (benchmark/roofline/ssm_dense.py `decode_step`, at the
context the records say was alive, `global_kv_tokens`). It grows with the
rows and not with the context."""

from benchmark import manifest


def read(collected: dict):
    step_reader = manifest.load_module("layer_metrics",
                                       "kernel.ssm_dense_step_roofline")
    step = step_reader.per_step(collected, step_reader.counted(collected))
    if step is None:
        return None
    w = manifest.load_module("roofline", step_reader.ROOFLINE).decode_step(
        collected["config"], collected["engine"], **step)
    return 100.0 * w["state_bytes"] / w["bytes"]
