"""Kernels: the `paged_latent_decode` kernel's roofline share in a family of
double layers — `kernel.paged_latent_decode_roofline`'s reading (its
account, `roofline/latent_moe.latent_decode_call`, reads only keys this
family's configuration has too) where a call is one attention SUB-layer of
one step and a page of latents is read once for all 64 heads. Under a name
of its own because the accepted entry lists another cell."""

from benchmark import manifest


def read(collected: dict):
    if "zero_expert_num" not in collected["config"]:
        return None
    return manifest.load_module(
        "layer_metrics", "kernel.paged_latent_decode_roofline").read(collected)
