"""Operations and bytes of a shortcut-connected mixture over double layers
(`models/longcat_flash.py`), from the configuration's shapes — by the names
its own `config.json` gives them: `num_layers`, `ffn_hidden_size`,
`expert_ffn_hidden_size`, `moe_topk`, `zero_expert_num` — and the program's
own counters, and the names its kernels carry in a device trace. A layer has
TWO attention sub-layers, and `n_routed_experts` are the experts THIS CHIP
holds of the `expert_parallel.experts` the router scores. Each account is of
the WORK, whatever implements it. Three accounts:

  latent_decode_call   one call of `paged_latent_decode`: one SUB-layer's
                       absorbed attention of one new token per sequence
                       (roofline/latent_moe.py's account, imported: it reads
                       only keys this configuration has).
  held_experts         the grouped expert products of a set of step records:
                       the THREE matrices of every held expert the records'
                       `experts_touched` counted, read once, and a
                       multiply-add per held assignment and matrix element.
                       An assignment of a zero-compute expert is no product
                       and one of another chip's expert is not computed
                       here: neither is in this account.
  decode_step          one step of the burst decode program: every weight
                       but the embedding table (a step reads one row of it a
                       sequence), the held experts counted as touched and not
                       as held, plus the latent cache alive in all the
                       attention sub-layers. Its operations count a token's
                       held assignments at the share of the router's outputs
                       the chip holds, and the identity experts as nothing.
"""

from __future__ import annotations

from benchmark.roofline.latent_moe import (  # noqa: F401 — the same work
    LATENT_DECODE_OPS,
    ROUTED_EXPERT_OPS,
    _itemsize,
    latent_decode_call,
)

SUBLAYERS = 2  # attention (and dense feed-forward) sub-layers a layer


def attention_layers(hf: dict) -> int:
    """Layers of the latent page pool."""
    return SUBLAYERS * hf["num_layers"]


def expert_params(hf: dict) -> int:
    """One routed expert's three matrices."""
    return 3 * hf["hidden_size"] * hf["expert_ffn_hidden_size"]


def expert_bytes(hf: dict) -> int:
    return expert_params(hf) * _itemsize(hf)


def held_slots(hf: dict) -> int:
    """Expert slots a step could touch here: layers x experts held."""
    return hf["num_layers"] * hf["n_routed_experts"]


def router_width(hf: dict) -> int:
    real = (hf.get("expert_parallel") or {}).get(
        "experts", hf["n_routed_experts"])
    return real + hf.get("zero_expert_num", 0)


def held_experts(hf: dict, *, experts_touched: float,
                 assignments: float) -> dict:
    rows = assignments * (2 * hf["hidden_size"]
                          + 3 * hf["expert_ffn_hidden_size"]) * _itemsize(hf)
    return {"flops": assignments * 2 * expert_params(hf),
            "bytes": experts_touched * expert_bytes(hf) + rows}


def decode_step(hf: dict, engine: dict, *, live_tokens: float, rows: float,
                experts_touched: float) -> dict:
    """`experts_touched`: distinct held experts a step reads, summed over
    the layers."""
    itemsize = _itemsize(hf)
    embed = hf["vocab_size"] * hf["hidden_size"]
    weights = (engine["param_bytes"] - embed * itemsize
               - (held_slots(hf) - experts_touched) * expert_bytes(hf))
    cache = (live_tokens * attention_layers(hf)
             * (hf["kv_lora_rank"] + hf["qk_rope_head_dim"]) * itemsize)
    held_share = hf["n_routed_experts"] / router_width(hf)
    active = (engine["n_params"] - embed - held_slots(hf) * expert_params(hf)
              + hf["num_layers"] * hf["moe_topk"] * held_share
              * expert_params(hf))
    attention = latent_decode_call(hf, live_tokens=live_tokens, rows=rows)
    return {"flops": 2 * active * rows
            + attention_layers(hf) * attention["flops"],
            "bytes": weights + cache}
