"""Plain float32 references of the configurations' architectures: straight
`jax.numpy`, no kernels, no cache, no batching. They read the program's
parameter pytree (names and layouts only) and nothing else of it.

A configuration names its reference: `correctness.reference` is the module
`reference/<name>.py`, beside the manifest that lists the configuration if
it has one of that name, else here. A module offers `forward(params, hf,
ids)` giving the logits [T, V]; one that declares `FOLLOWS = "routing"`
offers `forward(params, hf, ids, follow=None)` giving the logits and its own
router logits (reference/moe.py says why). `REFERENCES`, by the
configuration's `model_type`, serves only a file that names none."""

REFERENCES = {"mistral": "dense", "llama": "dense", "mixtral": "moe"}


def module_for(config: dict, base: str | None = None):
    """The reference module of a configuration; `base` is the directory of
    the manifest that lists it."""
    from benchmark import manifest

    name = config.get("correctness", {}).get("reference") or REFERENCES.get(
        config.get("model_type", "llama"))
    if name is None:
        raise manifest.ManifestError(
            f"configuration {config.get('model_id')!r} names no "
            "correctness.reference, and none is on file for its model_type "
            f"{config.get('model_type')!r}")
    return manifest.load_module("reference", name, base or manifest.ROOT)
