"""Model step: prompt tokens a device-second where a prompt of 12-16k tokens
is prefilled in chunks of 512, each chunk's full layers scoring and choosing
2,048 of the cells before it (`models/dots3_note.py`) — the tokens of the
traced window's `prefill` step records over the device time of the prefill
and extend programs in the trace (`model.prefill_tok_per_s`'s reading, under
a name of its own because the accepted entry lists another cell and moves
another end-to-end metric). A caller's tokens wait on the other fifteen
callers' chunks, so this rate is most of `tpot_p50_s` here."""

from benchmark import manifest


def read(collected: dict):
    roofline = manifest.load_module("roofline", "sparse_latent")
    if not roofline.is_sparse(collected["config"]):
        return None
    return manifest.load_module(
        "layer_metrics", "model.prefill_tok_per_s").read(collected)
