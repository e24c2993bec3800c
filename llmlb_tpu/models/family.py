"""What a model family IS, said once: the `Family` record.

Each family module instantiates one as `FAMILY`; `models/__init__.py`
registers the modules and derives `family_for` and `config_from_hf` from the
records. The engine reads a record and never probes a module: a field
misspelt or missing is the dataclass's `TypeError` at import, not a silent
downgrade. The functions a record points at stay module-level functions of
the family under their own names, and so do its jitted entry points (the
benchmark's checks take them off the module).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Mapping, NamedTuple


class StepCounter(NamedTuple):
    """One int32 counter a paged serving call returns after its caches."""

    reduce: str  # over a burst's steps and an engine's life: "sum" or "max"
    # its key in /api/health .metrics and, behind `llmlb_engine_`, its
    # /metrics name (engine/metrics.py lays out one that is not a scalar)
    export: str


@dataclasses.dataclass(frozen=True)
class Family:
    name: str  # the module is llmlb_tpu.models.<name>
    config_class: type
    model_types: tuple[str, ...]  # the `model_type`s of config.json it reads
    # keys of config.json that change the function computed and that its
    # configuration class reads (models/__init__.config_from_hf)
    mechanism_keys: tuple[str, ...]
    # -- the pool
    kv_token_layer_bytes: Callable  # (cfg, quantized=False) -> bytes
    kv_wire_cell: Callable  # (cfg) -> (kv heads, head dim); None: ships none
    kv_pool_layers: Callable = lambda cfg: cfg.num_layers  # that attend
    # (cfg) -> bytes a SLOT holds beside its pages (docs/hybrid-state.md):
    # its pool is made for `num_slots`, its prefills told their `slot_ids`
    state_slot_bytes: Callable | None = None
    pool: str = "page pool"  # in words, for `refuse`
    # -- decoding
    # (cfg) -> B; above 1 a decode step is a block pass of generation by
    # diffusion over blocks (docs/block-diffusion.md), whose parameters
    # check_generation holds to what has a meaning (ValueError)
    block_length: Callable = lambda cfg: 1
    check_generation: Callable | None = None
    # exports `verify_step_paged` (a recurrent state cannot take a rejected
    # draft back) / `make_context_parallel_prefill` (ring attention runs a
    # dense feed-forward: a mixture has none)
    verifies_drafts: bool = True
    context_parallel_prefill: bool = False
    # exports `mixed_step_paged`: a decode step with ONE arrival's prompt
    # prefilled in the same pass over the weights (llama._mixed_paged_impl).
    # A family sets it once its own equality test against prefill-then-decode
    # passes; a state per slot needs its mixer called twice a layer first
    mixed_step: bool = False
    # -- what it serves; the engine refuses the rest at start-up (`refuse`)
    int8_weights: bool = True
    int8_kv: bool = True
    lora: bool = True
    # -- counters a call may return, and (cfg) -> {name: shape} of those a
    # configuration's calls do return
    counters: Mapping[str, StepCounter] = dataclasses.field(
        default_factory=dict)
    step_counters: Callable = lambda cfg: {}
    # -- keywords its entry points take BEHIND llama's parameters (each with
    # a default that serves a caller with one row): on every paged entry
    # point, and behind those on one entry point alone
    paged_keywords: tuple[str, ...] = ()
    keywords_of: Mapping[str, tuple[str, ...]] = dataclasses.field(
        default_factory=dict)

    def refuse(self, *, int8_weights: bool = False, int8_kv: bool = False,
               lora: bool = False) -> None:
        """Raise NotImplementedError for what is asked of this family and it
        does not serve, rather than serve it half done."""
        module = f"llmlb_tpu.models.{self.name}"
        if int8_weights and not self.int8_weights:
            raise NotImplementedError(
                f"{module} does not serve int8 weights: some "
                "of its projections would be quantized and others not; "
                "start it without --quantize weights|all")
        if lora and not self.lora:
            raise NotImplementedError(
                f"{module} carries no adapter pools: "
                "start it without --lora-dir")
        if int8_kv and not self.int8_kv:
            raise NotImplementedError(
                f"an int8 {self.pool} is not implemented: serve "
                f"{self.name} models without kv quantization (quantize "
                "modes kv and all are refused for this family)")
