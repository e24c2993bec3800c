"""`engine/programs.py`: the one place that builds, caches and calls the
device programs.

The decode burst has ONE builder with the device grammar as a static flag:
without it the program takes no table and carries no cursor — the operands
and the donation of the plain burst the engine always dispatched — and with
it exactly the table and the cursor more. What one family's entry points
take and another's do not (`slot_ids`, `num_slots`) is added here, from the
family's record, and by nobody else.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmlb_tpu.engine import programs as programs_mod
from llmlb_tpu.engine.presets import get_preset
from llmlb_tpu.engine.programs import StepPrograms
from llmlb_tpu.models import FAMILIES, family_for, nemotron_h
from llmlb_tpu.parallel.mesh import MeshConfig, build_mesh

SLOTS, PAGES, PAGE_SIZE, WINDOW, BURST = 4, 9, 16, 256, 4
PRESETS = {"llama": "debug-tiny", "mixtral": "debug-moe-tiny",
           "deepseek_v3": "debug-mla-tiny", "sdar_moe": "debug-sdar-tiny",
           "nemotron_h": "debug-nemotron-h-tiny",
           "longcat_flash": "debug-longcat-tiny"}


def _programs(preset: str) -> StepPrograms:
    cfg = get_preset(preset)
    mesh = build_mesh(MeshConfig(dp=1, tp=1), devices=jax.devices()[:1])
    return StepPrograms(family_for(cfg), cfg, mesh, decode_burst=BURST,
                        max_draft_tokens=4, num_slots=SLOTS,
                        slot_capacity=512, eos_id=-1)


def _burst_operands(programs: StepPrograms):
    """Shapes of what the scheduler hands the dense burst (its
    `_decode_operands` and the live rows)."""
    module, cfg = programs.module, programs.cfg
    params = jax.eval_shape(lambda key: module.init_params(cfg, key),
                            jax.random.PRNGKey(0))
    ck, cv = jax.eval_shape(
        lambda: module.init_kv_pages(cfg, PAGES, PAGE_SIZE))

    def row(dtype):
        return jax.ShapeDtypeStruct((SLOTS,), dtype)

    tables = jax.ShapeDtypeStruct((SLOTS, 2), jnp.int32)
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    return (params, row(jnp.int32), row(jnp.int32), ck, cv, tables,
            row(jnp.float32), row(jnp.float32), row(jnp.int32),
            row(jnp.int32), key, row(jnp.bool_))


def _leaves(tree) -> int:
    return len(jax.tree.leaves(tree))


@pytest.mark.parametrize("preset", ["debug-tiny", "debug-moe-tiny"])
def test_the_burst_without_a_grammar_is_the_plain_burst(preset):
    programs = _programs(preset)
    operands = _burst_operands(programs)
    plain = programs.decode_many(WINDOW, grammar=False).lower(*operands)
    args, kwargs = plain.args_info
    # no table, no cursor: the operands are the plain burst's twelve
    assert not jax.tree.leaves(kwargs)
    assert _leaves(args) == _leaves(operands)
    donated = [i for i, arg in enumerate(args)
               if any(leaf.donated for leaf in jax.tree.leaves(arg))]
    assert donated == [3, 4]  # the two pools, whole
    assert all(leaf.donated for i in donated
               for leaf in jax.tree.leaves(args[i]))
    text = plain.as_text()
    assert "jit_many" in text  # the name the benchmark reads off the trace

    table = jax.ShapeDtypeStruct((8, programs.cfg.vocab_size), jnp.int32)
    cursor = jax.ShapeDtypeStruct((SLOTS,), jnp.int32)
    gram = programs.decode_many(WINDOW, grammar=True).lower(
        *operands, gram_table=table, gram_state=cursor)
    g_args, g_kwargs = gram.args_info
    assert sorted(g_kwargs) == ["gram_state", "gram_table"]
    assert _leaves(g_args) == _leaves(args) and _leaves(g_kwargs) == 2
    assert not any(leaf.donated for leaf in jax.tree.leaves(g_kwargs))
    assert gram.as_text() != text


def test_there_is_one_cache_and_engines_of_one_config_share_executables():
    programs = _programs("debug-tiny")
    plain = programs.decode_many(WINDOW)
    assert programs.decode_many(WINDOW, grammar=False) is plain
    gram = programs.decode_many(WINDOW, grammar=True)
    fused = programs.verify(WINDOW, fused=True, grammar=False)
    legacy = programs.verify(WINDOW, fused=False)
    assert len({id(f) for f in (plain, gram, fused, legacy)}) == 4
    assert sorted(programs.cache) == [
        ("decode_many", BURST, WINDOW, False),
        ("decode_many", BURST, WINDOW, True),
        ("verify", 4, WINDOW, False),
        ("verify_fused", 4, WINDOW, False),
    ]
    # a second engine of the same config and mesh: the same wrappers
    other = StepPrograms(programs.module, programs.cfg, programs.mesh,
                         decode_burst=BURST, max_draft_tokens=4,
                         num_slots=SLOTS, slot_capacity=512, eos_id=-1)
    assert other.decode_many(WINDOW) is plain
    assert other.decode_many(WINDOW, grammar=True) is gram
    assert other.verify(WINDOW, fused=True) is fused
    assert other.verify(WINDOW, fused=False) is legacy
    keys = {key[0] for key in programs_mod._PROGRAM_CACHE}
    assert {"decode_many", "decode_many_gram", "verify_fused",
            "verify"} <= keys


@pytest.mark.parametrize("family", sorted(PRESETS))
def test_only_a_family_with_state_per_slot_is_told_its_rows_slots(
        family, monkeypatch):
    programs = _programs(PRESETS[family])
    module = programs.module
    assert module in FAMILIES
    seen: dict[str, dict] = {}

    def spy(name):
        def call(*args, **kwargs):
            seen[name] = kwargs
            return None
        return call

    for name in ("prefill_into_pages", "prefill_extend_pages",
                 "decode_step_paged"):
        monkeypatch.setattr(module, name, spy(name))
    rows = np.zeros((2,), np.int32)
    programs.prefill(None, rows, rows, rows, None, None, slot_ids=[2, 3])
    programs.extend(None, rows, rows, rows, rows, None, None, slot_ids=[1])
    programs.decode_step(None, rows, rows, None, None, rows, window=WINDOW,
                         live=rows)
    slotted = module is nemotron_h
    assert slotted == (module.FAMILY.state_slot_bytes is not None)
    for name in ("prefill_into_pages", "prefill_extend_pages"):
        assert ("slot_ids" in seen[name]) == slotted
        assert set(seen[name]) - {"slot_ids"} == {"lora_idx"}
    if slotted:
        assert seen["prefill_into_pages"]["slot_ids"].tolist() == [2, 3]
        assert seen["prefill_extend_pages"]["slot_ids"].tolist() == [1]
    assert set(seen["decode_step_paged"]) == {"window", "lora_idx", "live"}

    def pool(*args, **kwargs):
        seen["init_kv_pages"] = kwargs
        return init(*args, **kwargs)

    init = module.init_kv_pages
    monkeypatch.setattr(module, "init_kv_pages", pool)
    programs.fresh_kv_pool(PAGES, PAGE_SIZE, False)
    assert seen["init_kv_pages"] == {
        "quantized": False, **({"num_slots": SLOTS} if slotted else {})}
