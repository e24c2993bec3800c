"""How a family's parameters are seeded, named and sharded, said once.

A family states a table `name -> (shape of one layer's leaf, fan-in; 0 = its
own rule)`, which stacks of layers hold which names, a rule for the leaves
that are no fan-in scaled matrix, and the logical axes of the leaves it
shards (parallel/sharding.py). The three functions here are the bodies of its
`init_params`, `param_logical_axes` and `param_shardings`, which stay
module-level names of the family (`models/family.py` says why).
"""

from __future__ import annotations

from typing import Callable, Iterable, Mapping, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from llmlb_tpu.parallel.mesh import validate_tp
from llmlb_tpu.parallel.sharding import ShardingRules, logical_to_sharding

F32 = jnp.float32


class Leaf(NamedTuple):
    """One stacked leaf [layers, *shape] of a family's pytree."""

    key: str  # in the pytree: a stack's prefix and the name
    name: str  # a layer's name for it: what the own rule and the axes read
    shape: tuple  # of one layer's leaf
    fan_in: int  # 0: the family's own rule
    layers: int


def stack_leaves(shapes: Mapping[str, tuple[tuple, int]],
                 stacks: Iterable[tuple[str, Iterable[str], int]]
                 ) -> list[Leaf]:
    """The leaves of `(prefix, names, layers)` stacks, in their order; a
    stack of no layers has none."""
    return [Leaf(prefix + name, name, *shapes[name], layers)
            for prefix, names, layers in stacks if layers > 0
            for name in names]


def ones(cfg, name: str, key, shape):
    """The own rule of a family whose every leaf of fan-in 0 is a norm."""
    return jnp.ones(shape, cfg.dtype)


def seeded_bias(sd: float) -> Callable:
    """The own rule of a mixture whose router's choice bias is a seeded
    normal of standard deviation `sd` in float32, NOT zero
    (deepseek_v3.init_params says why), and whose other leaves of fan-in 0
    are norms."""
    def own_rule(cfg, name: str, key, shape):
        if name == "router_bias":
            return sd * jax.random.normal(key, shape, F32)
        return ones(cfg, name, key, shape)
    return own_rule


def init_params(cfg, key: jax.Array, leaves: list[Leaf],
                own_rule: Callable = ones, *, head_last: bool = False
                ) -> dict[str, jax.Array]:
    """Random init (serving uses checkpoint weights; this backs tests and
    the benchmark): a key a leaf, in the family's order behind the table's
    and the head's; a matrix normal x fan_in^-0.5 drawn in float32 and cast
    to `cfg.dtype`, a leaf of fan-in 0 `own_rule(cfg, name, key, shape)`.
    A config that ties the head to the table has no `lm_head`, and the
    head's key is not drawn. `head_last` draws the head behind the leaves
    (deepseek_v3's order, which its seeded weights keep)."""
    keys = iter(jax.random.split(key, len(leaves) + 2))
    e = cfg.hidden_size

    def w(k, shape, fan_in):
        return (jax.random.normal(k, shape, F32) * fan_in**-0.5
                ).astype(cfg.dtype)

    def head():
        if not cfg.tie_word_embeddings:
            params["lm_head"] = w(next(keys), (e, cfg.vocab_size), e)

    params = {"embed": w(next(keys), (cfg.vocab_size, e), e),
              "ln_final": jnp.ones((e,), cfg.dtype)}
    if not head_last:
        head()
    for leaf in leaves:
        k, shape = next(keys), (leaf.layers, *leaf.shape)
        params[leaf.key] = (w(k, shape, leaf.fan_in) if leaf.fan_in
                            else own_rule(cfg, leaf.name, k, shape))
    if head_last:
        head()
    return params


# The logical axes of the leaves most families hold under these names
# (parallel/sharding.py), for a family's `axes`; one it has no leaf of is
# never read.
GQA_AXES = {"wq": ("embed", "heads"), "wk": ("embed", "kv_heads"),
            "wv": ("embed", "kv_heads"), "wo": ("heads", "embed")}
MLP_AXES = {"wg": ("embed", "ffn"), "wu": ("embed", "ffn"),
            "wd": ("ffn", "embed")}
EXPERT_AXES = {"we_gate": ("experts", "embed", "ffn"),
               "we_up": ("experts", "embed", "ffn"),
               "we_down": ("experts", "ffn", "embed"),
               "ws_gate": ("embed", "ffn"), "ws_up": ("embed", "ffn"),
               "ws_down": ("ffn", "embed")}


def param_logical_axes(cfg, leaves: list[Leaf],
                       axes: Mapping[str, tuple]) -> dict[str, tuple]:
    """Logical sharding axes of every leaf: `axes[name]` behind "layers", a
    leaf `axes` does not name replicated."""
    out = {"embed": ("vocab", "embed"), "ln_final": ("embed",)}
    if not cfg.tie_word_embeddings:
        out["lm_head"] = ("embed", "vocab")
    for leaf in leaves:
        out[leaf.key] = ("layers", *axes.get(
            leaf.name, (None,) * len(leaf.shape)))
    return out


def shard_rules_for(cfg, tp: int) -> ShardingRules:
    """Default rules; kv heads replicate when tp exceeds the kv head count."""
    validate_tp(cfg.num_heads, cfg.num_kv_heads, tp)
    if cfg.intermediate_size % tp != 0:
        raise ValueError(
            f"intermediate_size={cfg.intermediate_size} not divisible by tp={tp}"
        )
    kv_shardable = cfg.num_kv_heads % tp == 0
    return ShardingRules(kv_heads="tp" if kv_shardable else None)


def param_shardings(cfg, mesh: Mesh, rules: ShardingRules | None,
                    axes: Mapping[str, tuple]):
    """A NamedSharding a leaf from a family's `param_logical_axes(cfg)`."""
    rules = rules or shard_rules_for(cfg, mesh.shape["tp"])
    return {name: logical_to_sharding(mesh, rules, *a)
            for name, a in axes.items()}
