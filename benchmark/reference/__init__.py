"""Plain float32 references of the configurations' architectures: straight
`jax.numpy`, no kernels, no cache, no batching. They read the program's
parameter pytree (names and layouts only) and nothing else of it."""

REFERENCES = {"mistral": "dense", "llama": "dense", "mixtral": "moe"}
