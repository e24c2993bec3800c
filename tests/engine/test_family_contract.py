"""The serving contract a model family provides, held name by name.

`models.family_for(cfg)` hands the scheduler a module of free functions and
the scheduler calls them positionally (`engine/scheduler.py`); a missing
name is a start-up `AttributeError` or a silent `hasattr` downgrade, and a
drifted parameter is a `TypeError` at the first dispatch. This holds every
family to llama's signatures up front, so the next family is added against
a test and not against a traceback.
"""

import inspect

import pytest

from llmlb_tpu.engine.presets import get_preset
from llmlb_tpu.models import family_for, llama

# one config per module family_for can return
FAMILIES = {
    "llama": family_for(get_preset("debug-tiny")),
    "mixtral": family_for(get_preset("debug-moe-tiny")),
    "deepseek_v3": family_for(get_preset("debug-mla-tiny")),
    "sdar_moe": family_for(get_preset("debug-sdar-tiny")),
    "nemotron_h": family_for(get_preset("debug-nemotron-h-tiny")),
    "longcat_flash": family_for(get_preset("debug-longcat-tiny")),
}
PRESETS = {"llama": "debug-tiny", "mixtral": "debug-moe-tiny",
           "deepseek_v3": "debug-mla-tiny", "sdar_moe": "debug-sdar-tiny",
           "nemotron_h": "debug-nemotron-h-tiny",
           "longcat_flash": "debug-longcat-tiny"}
# Static switches a family may add BEHIND llama's parameters, keyword-only
# in effect: the benchmark's check passes `routing` (models/deepseek_v3.py),
# never positionally. `slot_ids`: the rows' slots, for a family that keeps
# a state per slot beside the page pool (models/nemotron_h.py); the default
# is row i in slot i, which serves the benchmark's check and its one row.
EXTRA = {"deepseek_v3": ["routing"], "sdar_moe": ["routing"],
         "nemotron_h": ["routing", "slot_ids"],
         "longcat_flash": ["routing"]}
# What a family may add behind llama's parameters elsewhere: the slot count
# of a pool with a state per slot (default 1); the scheduler knows such a
# family by its `state_slot_bytes` (EngineCore._slot_state). And behind the
# switches above, on a block family's pass alone: one block's logits a row
# from the row's own offset into a chunk of two (scheduler._build_block_many;
# the default is every position's, which the benchmark's check takes).
EXTRA_OF = {("nemotron_h", "init_kv_pages"): ["num_slots"],
            ("sdar_moe", "verify_step_paged"): ["logits_from", "logits_len"]}

CONTRACT = (
    "init_params",
    "param_shardings",
    "init_kv_pages",
    "kv_pages_shardings",
    "prefill_into_pages",
    "prefill_extend_pages",
    "verify_step_paged",
    "decode_step_paged",
    "make_context_parallel_prefill",
)
# Found by `hasattr` and served without when absent: ring-attention prefill
# runs llama's dense feed-forward, so a mixture of experts must not export it.
OPTIONAL = {"make_context_parallel_prefill"}
# Absent BY DESIGN, family by family, and asked of every other: a family
# with a recurrent state cannot verify a draft (a rejected token would leave
# the state advanced, and there is no snapshot to roll back to), so it
# exports no `verify_step_paged` and the scheduler's `hasattr`
# (`_spec_available`) serves it without speculation; an engine asked for
# speculation with it does not start (`_check_slot_state_engine`).
ABSENT_BY_DESIGN = {"nemotron_h": {"verify_step_paged"}}


def _params(fn) -> list[tuple[str, inspect._ParameterKind]]:
    """Names and kinds, in order: what a positional or a keyword call binds
    to. Annotations and defaults may differ (a family names its own config
    class); `inspect.signature` sees through `jax.jit`."""
    return [(p.name, p.kind)
            for p in inspect.signature(fn).parameters.values()]


def test_every_family_module_is_covered():
    assert FAMILIES["llama"] is llama
    assert len({id(m) for m in FAMILIES.values()}) == len(FAMILIES)


@pytest.mark.parametrize("name", CONTRACT)
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_family_provides_the_paged_contract(family, name):
    module = FAMILIES[family]
    if not hasattr(module, name):
        assert name in OPTIONAL | ABSENT_BY_DESIGN.get(family, set()), (
            f"{family} lacks {name}")
        return
    assert name not in ABSENT_BY_DESIGN.get(family, set()), (
        f"{family} exports {name}, which it cannot serve")
    want = _params(getattr(llama, name))
    got = _params(getattr(module, name))
    paged = name in ("prefill_into_pages", "prefill_extend_pages",
                     "verify_step_paged", "decode_step_paged")
    extra = ((EXTRA.get(family, []) if paged else [])
             + EXTRA_OF.get((family, name), []))
    assert got[:len(want)] == want and [n for n, _ in got[len(want):]] == extra, (
        f"{family}.{name} takes other parameters than llama.{name}"
    )


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_family_says_what_a_token_leaves_in_the_pool(family):
    """The scheduler's page bytes, gauges and KVSH header ask the family."""
    module = FAMILIES[family]
    cfg = get_preset(PRESETS[family])
    ck, cv = map(llama._pages, module.init_kv_pages(cfg, 3, 8))
    per_token = (ck[0, 0, 0].size + cv[0, 0, 0].size) * ck.dtype.itemsize
    assert module.kv_token_layer_bytes(cfg) == per_token
    cell = module.kv_wire_cell(cfg)
    assert cell is None or cell == ck.shape[-2:] == cv.shape[-2:]


def test_an_added_parameter_has_a_default_that_serves_one_row():
    """What a family adds behind llama's parameters is optional: the
    benchmark's check (benchmark/correctness.py) calls every family with
    llama's arguments alone."""
    for family, names in EXTRA.items():
        for fn in ("prefill_into_pages", "prefill_extend_pages",
                   "decode_step_paged"):
            params = inspect.signature(getattr(FAMILIES[family], fn)).parameters
            assert all(params[n].default in (False, None) for n in names)
    for (family, fn), names in EXTRA_OF.items():
        params = inspect.signature(getattr(FAMILIES[family], fn)).parameters
        assert all(params[n].default in (None, 1) for n in names)
