"""The paged programs read and write the stacked KV pool in place.

A `pallas_call` takes whole buffers as operands, so a decode program that
hands the attention kernel `pool[layer]` makes XLA copy one layer of the pool
per call: on the chip that was 105 MB per layer, K and V, a third of the
decode step (PERF.md §6, PR 25). And a `lax.scan` that takes the pool as
`xs` and gives it back as `ys` copies the pool whole and slices every layer
out of it and back, per call: 25 ms of a 45 ms prefill step (PERF.md §6,
PR 32). Every program now passes the 5-D pool and a layer index, prefill and
extend with the pool as the layer scan's carry, and these tests keep a later
refactor from bringing a slice or a copy back: in the program as traced (the
jaxpr), and in the program as the TPU's compiler leaves it for a described
v5e. Its time is the benchmark's to show."""

import collections
import dataclasses
import functools
import inspect
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import pytest

from llmlb_tpu.engine.presets import get_preset
from llmlb_tpu.models import (
    deepseek_v3,
    llama,
    longcat_flash,
    mimo_v2,
    mixtral,
    nemotron_h,
    sdar_moe,
)
from llmlb_tpu.ops import pallas_attention, pallas_moe, ssm

LAYERS, PAGES, PAGE_SIZE, KV_HEADS, HEAD_DIM = 3, 7, 8, 2, 16
ROWS, PAGES_PER_ROW = 2, 3
DIMS = dict(vocab_size=96, hidden_size=64, intermediate_size=80,
            num_layers=LAYERS, num_heads=4, num_kv_heads=KV_HEADS,
            dtype=jnp.float32)
FAMILIES = {
    "llama": (llama, llama.LlamaConfig(**DIMS)),
    "mixtral": (mixtral, mixtral.MixtralConfig(
        **DIMS, num_experts=4, experts_per_token=2)),
}


# the pages a grid step of the decode kernels takes at these shapes (the
# window of `_decode_jaxpr` is two pages)
DECODE_GROUP = pallas_attention.decode_group(
    PAGE_SIZE * KV_HEADS * 2 * HEAD_DIM, 2)


def _equations(jaxpr, kernel_bodies=False):
    """Every equation of a jaxpr and of the jaxprs nested in it (jit, scan,
    cond …), except the bodies of Pallas kernels (their refs are blocks)
    unless `kernel_bodies`."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call" and not kernel_bodies:
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub, kernel_bodies)


def _is_expert_kernel(eqn):
    return "grouped_expert_matmul" in str(
        eqn.params.get("name_and_src_info", eqn.params.get("name")))


def _decode_jaxpr(family, cfg, quantized, monkeypatch):
    monkeypatch.setenv("LLMLB_TPU_ATTENTION", "pallas")  # read while tracing
    family.decode_step_paged._clear_cache()
    params = jax.eval_shape(lambda key: family.init_params(cfg, key),
                            jax.random.PRNGKey(0))
    cache_k, cache_v = jax.eval_shape(
        lambda: family.init_kv_pages(cfg, PAGES, PAGE_SIZE,
                                     quantized=quantized))
    rows = jax.ShapeDtypeStruct((ROWS,), jnp.int32)
    tables = jax.ShapeDtypeStruct((ROWS, PAGES_PER_ROW), jnp.int32)
    try:
        closed = jax.make_jaxpr(
            lambda p, ids, lens, ck, cv, t: family.decode_step_paged(
                p, cfg, ids, lens, ck, cv, t, window=2 * PAGE_SIZE)
        )(params, rows, rows, cache_k, cache_v, tables)
    finally:
        family.decode_step_paged._clear_cache()  # traced with the env set
    return closed.jaxpr


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_paged_decode_hands_the_kernel_the_stacked_pool(name, quantized,
                                                        monkeypatch):
    family, cfg = FAMILIES[name]
    values = (LAYERS, PAGES, PAGE_SIZE, KV_HEADS, HEAD_DIM)
    layer_scales = values[1:-1]  # [P, PS, K]
    # What each kernel must be given: K and V values whole. An int8 pool's
    # scales go in as the layer's slice: an f32 operand with 8 heads minor
    # is re-laid-out for Mosaic whatever its rank, which is cheapest on one
    # layer (paged_flash_decode_quant's docstring). They are the only
    # per-layer piece of the pool the program may hold.
    # The bf16 kernel takes the pool under the view [L, P, PS*K, D], a page
    # as the rows it is stored as (a bitcast on the chip: the compiled
    # burst below holds no copy under either shape).
    # Each operand goes in once a page of the group a grid step takes (two
    # at this window of two pages): the same buffer under another block.
    rows = (LAYERS, PAGES, PAGE_SIZE * KV_HEADS, HEAD_DIM)
    given = values if quantized else rows
    kernel_operands = DECODE_GROUP * (
        [given, given] + [layer_scales] * (2 * quantized))
    never = {values[1:], rows[1:]} | (set() if quantized else {layer_scales})

    eqns = list(_equations(_decode_jaxpr(family, cfg, quantized,
                                         monkeypatch)))
    sliced = [str(eqn) for eqn in eqns
              if any(getattr(v.aval, "shape", None) in never
                     for v in eqn.outvars)]
    assert not sliced, f"a per-layer slice of the pool is back: {sliced}"

    calls = [eqn for eqn in eqns if eqn.primitive.name == "pallas_call"]
    # a mixture's experts are a kernel too (ONE a layer at a decode step's
    # few rows, which it multiplies where they stand: PR 63), handed the
    # experts of every layer: [L, X, K, O], never one layer's slice
    experts = [eqn for eqn in calls if _is_expert_kernel(eqn)]
    assert len(experts) == (LAYERS if name == "mixtral" else 0)
    for eqn in experts:
        assert [v.aval.shape[0] for v in eqn.invars
                if len(v.aval.shape) == 4] == [LAYERS] * 3
    kernels = [eqn for eqn in calls if not any(eqn is e for e in experts)]
    assert len(kernels) == LAYERS
    q_rows = (ROWS, DIMS["num_heads"], HEAD_DIM)  # [B, H, D], head-major
    for eqn in kernels:
        shapes = [v.aval.shape for v in eqn.invars]
        assert sorted(s for s in shapes if len(s) >= 3) == sorted(
            kernel_operands + [q_rows]), shapes


MIXED_WIDTH = 2 * PAGE_SIZE  # the arrival's prompt, padded: two pages


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_the_mixed_step_hands_the_kernels_the_stacked_pool(name, quantized,
                                                           monkeypatch):
    """The decode step that carries an arrival's prompt
    (llama._mixed_paged_impl) passes the assertions the decode step passes:
    no per-layer slice of the pool, the decode kernel handed the pool whole
    with the layer's index a layer, and beside it ONE prefill kernel a layer
    over the prompt's own fresh keys and values — which are the only values
    of a prompt's size the program holds."""
    family, cfg = FAMILIES[name]
    monkeypatch.setenv("LLMLB_TPU_ATTENTION", "pallas")  # read while tracing
    family.mixed_step_paged._clear_cache()
    params = jax.eval_shape(lambda key: family.init_params(cfg, key),
                            jax.random.PRNGKey(0))
    cache_k, cache_v = jax.eval_shape(
        lambda: family.init_kv_pages(cfg, PAGES, PAGE_SIZE,
                                     quantized=quantized))
    rows = jax.ShapeDtypeStruct((ROWS,), jnp.int32)
    try:
        jaxpr = jax.make_jaxpr(
            lambda p, ids, lens, ck, cv, t, prompt, n, row:
            family.mixed_step_paged(p, cfg, ids, lens, ck, cv, t, prompt, n,
                                    row, window=2 * PAGE_SIZE)
        )(params, rows, rows, cache_k, cache_v,
          jax.ShapeDtypeStruct((ROWS, PAGES_PER_ROW), jnp.int32),
          jax.ShapeDtypeStruct((1, MIXED_WIDTH), jnp.int32),
          jax.ShapeDtypeStruct((1,), jnp.int32),
          jax.ShapeDtypeStruct((), jnp.int32)).jaxpr
    finally:
        family.mixed_step_paged._clear_cache()  # traced with the env set
    values = (LAYERS, PAGES, PAGE_SIZE, KV_HEADS, HEAD_DIM)
    stored = (LAYERS, PAGES, PAGE_SIZE * KV_HEADS, HEAD_DIM)
    layer_scales = values[1:-1]
    never = {values[1:], stored[1:]} | (set() if quantized
                                        else {layer_scales})
    eqns = list(_equations(jaxpr))
    sliced = [str(eqn) for eqn in eqns
              if any(getattr(v.aval, "shape", None) in never
                     for v in eqn.outvars)]
    assert not sliced, f"a per-layer slice of the pool is back: {sliced}"
    calls = [eqn for eqn in eqns if eqn.primitive.name == "pallas_call"
             and not _is_expert_kernel(eqn)]
    whole = values if quantized else stored
    over_the_pool = [eqn for eqn in calls
                     if whole in [v.aval.shape for v in eqn.invars]]
    assert len(over_the_pool) == LAYERS  # the rows' decode, a layer
    assert len(calls) == 2 * LAYERS  # and the prompt's prefill, a layer
    for eqn in calls:
        if not any(eqn is e for e in over_the_pool):
            # over the prompt's own T tokens, and no cell of the pool
            big = [v.aval.shape for v in eqn.invars if len(v.aval.shape) >= 3]
            assert big and all(MIXED_WIDTH in shape and PAGES not in shape
                               for shape in big), big
    # the feed-forward once a layer over the B + T tokens: a mixture's one
    # kernel a layer, the decode step's count (few rows here; an engine's
    # mixed width is past `pallas_moe.ROWS_IN_PLACE` and takes the sorted
    # route's three)
    experts = [eqn for eqn in eqns if eqn.primitive.name == "pallas_call"
               and _is_expert_kernel(eqn)]
    assert len(experts) == (LAYERS if name == "mixtral" else 0)


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_a_decode_grid_step_is_one_pair_of_products(name, quantized,
                                                    monkeypatch):
    """The decode kernels take a group of pages as they are stored: every
    query head against all of its [G*PS*K, D] rows in ONE product, one
    softmax update and ONE product (PERF.md §6, PR 43, PR 54), whatever the
    number of KV heads and the group — no product a head or a page, and no
    loop over the heads or the group in the one body both kernels share."""
    family, cfg = FAMILIES[name]
    kernels = [eqn for eqn in _equations(_decode_jaxpr(family, cfg, quantized,
                                                       monkeypatch))
               if eqn.primitive.name == "pallas_call"
               and not _is_expert_kernel(eqn)]
    assert len(kernels) == LAYERS
    for eqn in kernels:
        products = [e for e in _equations(eqn.params["jaxpr"], True)
                    if e.primitive.name == "dot_general"]
        rows = DECODE_GROUP * PAGE_SIZE * KV_HEADS  # a group's cells x KV heads
        assert [tuple(v.aval.shape for v in e.invars) for e in products] == [
            ((DIMS["num_heads"], HEAD_DIM), (rows, HEAD_DIM)),  # q, keys
            ((DIMS["num_heads"], rows), (rows, HEAD_DIM)),  # weights, values
        ]
    body = inspect.getsource(pallas_attention._decode_item)
    assert "for " not in body.split('"""')[2], "a loop is back in the body"


# --- a group of pages a grid step: the body is written once ------------------

DECODE_VARIANTS = ["plain", "sink", "bound", "quant"]
# the kernel and its index maps as PR 52 (the parent of the group) traced them
# at OLMo-Hybrid's 32 stored heads, where the rule gives a group of 1
PARENT_FORMS = pathlib.Path(__file__).parent / "data" / "paged_decode_kernel_pr52"


def _decode_kernel_call(variant, kv_heads, groups, group=None, table=34):
    """The pallas_call equation of one paged decode call at a cell's page
    (128 cells, heads of 128), traced for the interpreter."""
    rows = 4
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    pool = jax.ShapeDtypeStruct((2, 40, 128, kv_heads, 128), jnp.bfloat16)
    q = jax.ShapeDtypeStruct((rows, kv_heads * groups, 128), jnp.bfloat16)
    tail = (ints(), ints(rows, table), ints(rows))
    name = {"sink": "sink", "bound": "kv_from"}.get(variant)
    extra = {"sink": jax.ShapeDtypeStruct((kv_heads * groups,), jnp.float32),
             "bound": ints(rows)}.get(variant)

    def fn(*operands):
        """The call as a decode program makes it: a `group` that is given
        comes as the work-list's (None: the kernel's own, by shape)."""
        named = {} if name is None else {name: operands[-1]}
        operands = operands[:len(operands) - len(named)]
        if group is not None:
            named["work"] = pallas_attention.decode_work_list(
                *operands[-2:], page_size=128, group=group,
                kv_from=named.get("kv_from"))
        kernel = (pallas_attention.paged_flash_decode_quant
                  if variant == "quant" else pallas_attention.paged_flash_decode)
        return kernel(*operands, interpret=True, **named)

    if variant == "quant":
        pool8 = jax.ShapeDtypeStruct(pool.shape, jnp.int8)
        scales = jax.ShapeDtypeStruct(pool.shape[1:4], jnp.float32)
        operands = (q, pool8, scales, pool8, scales, *tail)
    else:
        operands = (q, pool, pool, *tail) + (() if extra is None else (extra,))
    (call,) = [e for e in _equations(jax.make_jaxpr(fn)(*operands).jaxpr)
               if e.primitive.name == "pallas_call"]
    return call


# the two decode kernels over a pool without a head axis, at their cells'
# pages of 128: the latent kernel at kanana-2-30b-a3b's 32 heads on a latent
# of 512 and the rope's tile, the flat one at mimo-v2-5's 64 heads on 4 x 192
# keys and 4 x 128 values
HEADLESS = ["latent", "flat"]
# … and both as PR 57 (the parent of their group) traced them
HEADLESS_PARENT_FORMS = PARENT_FORMS.with_name("paged_decode_kernel_pr57")


def _headless_kernel_call(kind, group, table=34):
    """The pallas_call equation of one latent or flat paged decode call,
    traced for the interpreter, its work-list `group` pages an item (None:
    the kernel's own list, by shape)."""
    rows = 4
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    bf16 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)  # noqa: E731
    if kind == "latent":
        kernel = functools.partial(pallas_attention.paged_latent_decode,
                                   scale=192 ** -0.5)
        operands = (bf16(rows, 32, 512), bf16(rows, 32, 128),
                    bf16(2, 40, 128, 512), bf16(2, 40, 128, 128))
    else:
        kernel = functools.partial(pallas_attention.paged_flat_decode,
                                   num_kv=4)
        operands = (bf16(rows, 64, 192), bf16(2, 40, 128, 4 * 192),
                    bf16(2, 40, 128, 4 * 128))

    def fn(*operands):
        work = None if group is None else pallas_attention.decode_work_list(
            *operands[-2:], page_size=128, group=group)
        return kernel(*operands, work=work, interpret=True)

    closed = jax.make_jaxpr(fn)(*operands, ints(), ints(rows, table),
                                ints(rows))
    (call,) = [e for e in _equations(closed.jaxpr)
               if e.primitive.name == "pallas_call"]
    return call


def _body_census(call) -> collections.Counter:
    """How often each primitive occurs in a kernel's traced body."""
    return collections.Counter(
        e.primitive.name for e in _equations(call.params["jaxpr"], True))


def _kernel_form(call) -> str:
    """A kernel as text: its body, then each block's index map."""
    maps = [str(block.index_map_jaxpr)
            for block in call.params["grid_mapping"].block_mappings]
    return "\n".join([str(call.params["jaxpr"]), *maps]) + "\n"


@pytest.mark.parametrize("variant", DECODE_VARIANTS)
def test_at_a_group_of_one_the_decode_kernel_is_the_parents(variant):
    """Where the rule gives a group of 1 (32 stored heads: a page is two
    megabytes) the call traces to the kernel and the index maps it traced to
    before there were groups, text for text — and a ring, one page a row,
    has a group of 1 whatever its heads."""
    assert pallas_attention.decode_group(128 * 32 * (128 + 128), 34) == 1
    assert pallas_attention.decode_group(128 * 4 * (192 + 128), 1) == 1
    got = _kernel_form(_decode_kernel_call(variant, 32, 1))
    assert got == (PARENT_FORMS / f"{variant}.txt").read_text()


@pytest.mark.parametrize("kind", HEADLESS)
def test_at_a_group_of_one_the_headless_decode_kernel_is_the_parents(kind):
    """The latent and the flat kernel under a work-list of one page an item
    (a ring's sweep, a caller's own `work`) trace to the kernel and the
    index maps they traced to before they took groups (PR 57), text for
    text."""
    got = _kernel_form(_headless_kernel_call(kind, 1))
    assert got == (HEADLESS_PARENT_FORMS / f"{kind}.txt").read_text()


@pytest.mark.parametrize("variant", DECODE_VARIANTS)
@pytest.mark.parametrize("kv_heads,groups", [(2, 16), (4, 8)],
                         ids=["nemotron-K2xG16", "trinity-K4xG8"])
def test_the_decode_body_does_not_grow_with_the_group(kv_heads, groups,
                                                       variant):
    """What refused PR 53: a body written out once a page of the group is
    traced and lowered that many times, at every start. The kernel's jaxpr
    holds the same equations at a group of 8 as at a group of 2 — one
    product pair, one mask, one update — but for the reads of the group's
    block refs, one a block."""
    def census(group):
        return _body_census(
            _decode_kernel_call(variant, kv_heads, groups, group=group))

    small, large = census(2), census(8)
    operands = 4 if variant == "quant" else 2  # values, and an int8 pool's scales
    assert large - small == collections.Counter({"get": operands * (8 - 2)})
    assert not small - large
    assert large["dot_general"] == 2 and large["concatenate"] == operands
    assert large["exp"] == small["exp"] == census(1)["exp"]


@pytest.mark.parametrize("kind", HEADLESS)
def test_the_headless_decode_body_does_not_grow_with_the_group(kind):
    """The same guard for the latent and the flat kernel (PR 58): at a group
    of 4 the body holds the equations it holds at 2 — the latent kernel its
    two scores products and its mix, the flat one a pair — but for the reads
    of the group's block refs, one a block of either pool."""
    def census(group):
        return _body_census(_headless_kernel_call(kind, group))

    small, large = census(2), census(4)
    assert large - small == collections.Counter({"get": 2 * (4 - 2)})
    assert not small - large
    assert large["dot_general"] == (3 if kind == "latent" else 2)
    assert large["concatenate"] == 2
    assert large["exp"] == small["exp"] == census(1)["exp"]


def test_the_group_is_a_function_of_the_shapes():
    """As many pages as make a megabyte of bf16 keys and values, no more
    than the sweep and no more than 4 (a page of the group is a block
    operand every program traces: PERF.md §6, PR 54): the cells' widths."""
    def group(kv, d, dv, sweep, page_size=128):  # a pool with a head axis
        return pallas_attention.decode_group(page_size * kv * (d + dv), sweep)

    assert [group(kv, 128, 128, 34) for kv in (2, 4, 8, 32)] == [4, 4, 2, 1]
    assert [group(2, 128, 128, sweep) for sweep in (1, 2, 3, 17)] == [1, 2, 3, 4]
    assert group(4, 192, 128, 34) == 3  # narrower values count as they are
    assert group(2, 16, 16, 64, page_size=8) == 4  # a tiny page
    # the pools without a head axis, a page as it is stored: the latent of
    # 512 beside the rope's whole tile; 4 x 192 keys beside 4 x 128 values
    assert pallas_attention.decode_group(128 * (512 + 128), 34) == 4
    assert pallas_attention.decode_group(128 * (4 * 192 + 4 * 128), 34) == 3
    assert pallas_attention.decode_group(128 * (512 + 128), 2) == 2
    assert list(inspect.signature(pallas_attention.decode_group).parameters
                ) == ["page_elements", "sweep"]
    source = inspect.getsource(pallas_attention.decode_group)
    assert "environ" not in source and "name" not in source


@pytest.mark.parametrize("kind,want", [("latent", 4), ("flat", 3)])
def test_a_decode_step_builds_the_headless_pools_group_from_both_pools(
        kind, want, monkeypatch):
    """`paged_decode_work`, the one list a decode step builds for all its
    layers, counts a page in BOTH pools as they are stored: 4 pages an item
    for kanana-2-30b-a3b's and longcat-flash-omni's latent pools, 3 for
    mimo-v2-5's flat ones — and the kernel called without a list builds the
    same one."""
    from llmlb_tpu.ops import attention

    monkeypatch.setenv("LLMLB_TPU_ATTENTION", "pallas")
    call = _headless_kernel_call(kind, None)
    pools = [v.aval for v in call.invars if len(v.aval.shape) == 4]
    assert len(pools) == 2 * want  # each pool once a page of the group
    tables = jax.ShapeDtypeStruct((4, 34), jnp.int32)
    lens = jax.ShapeDtypeStruct((4,), jnp.int32)
    work = jax.eval_shape(attention.paged_decode_work, pools[0], pools[-1],
                          tables, lens)
    assert work.group == want
    assert jax.eval_shape(functools.partial(
        attention.paged_decode_work, window=2 * 128), pools[0], pools[-1],
        tables, lens).group == 2  # no more than the window's pages


# --- the state-space step kernel: one turn of one loop, whatever the shape -----

def _state_step_census(heads, groups, channels=64, state=128, slots=4):
    """The primitives of `ssm_decode_step`'s traced kernel body."""
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
    closed = jax.make_jaxpr(
        lambda *a: ssm.ssm_decode_step(*a, interpret=False))(
        f32((2, slots, heads, channels, state)),
        jax.ShapeDtypeStruct((), jnp.int32), f32((slots, heads)),
        f32((slots, heads, channels)), f32((slots, groups, state)),
        f32((slots, groups, state)))
    (call,) = [e for e in _equations(closed.jaxpr)
               if e.primitive.name == "pallas_call"]
    return _body_census(call)


@pytest.mark.parametrize("heads,groups", [(8, 1), (64, 2), (64, 8), (128, 8)],
                         ids=["H8xG1", "H64xG2", "H64xG8", "H128xG8"])
def test_the_state_step_body_does_not_grow_with_the_heads_or_the_groups(
        heads, groups):
    """The guard PR 54 gave the paged decode kernel, for the kernel a
    Granite decode program holds 36 call sites of: its traced body at 8
    heads, at 128, at two groups and at eight is the body at Granite's 64
    heads and one group, equation for equation (a body written out a head
    or a group is traced and lowered that many times at every start: what
    refused PR 53) — one loop, and in a turn of it one crossing each way."""
    want = _state_step_census(64, 1)
    assert _state_step_census(heads, groups) == want
    assert want["transpose"] == 2 and want["reduce_sum"] == 1
    assert want["scan"] + want["while"] == 1  # lax.fori_loop, either form


# --- the extend kernels: what a grid step does follows the q block -----------

# the block family's heads, page and pool as its cell serves them
EXTEND_KV, EXTEND_GROUPS, EXTEND_PAGE, EXTEND_PAGES = 4, 8, 128, 544


def _extend_operands(queries, quantized, kv=EXTEND_KV, groups=EXTEND_GROUPS,
                     rows=32, table=8, pages=EXTEND_PAGES):
    """(kernel, operand shapes) of one paged extend call at head size 128."""
    pool = jax.ShapeDtypeStruct((2, pages, EXTEND_PAGE, kv, 128),
                                jnp.int8 if quantized else jnp.bfloat16)
    scales = jax.ShapeDtypeStruct((pages, EXTEND_PAGE, kv), jnp.float32)
    q = jax.ShapeDtypeStruct((rows, queries, kv * groups, 128), jnp.bfloat16)
    tables = jax.ShapeDtypeStruct((rows, table), jnp.int32)
    lens = jax.ShapeDtypeStruct((rows,), jnp.int32)
    layer = jax.ShapeDtypeStruct((), jnp.int32)
    if quantized:
        return pallas_attention.paged_flash_extend_quant, (
            q, pool, scales, pool, scales, layer, tables, lens, lens)
    return pallas_attention.paged_flash_extend, (
        q, pool, pool, layer, tables, lens, lens)


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("queries", [8, 128])
def test_an_extend_grid_step_follows_the_q_block(queries, quantized):
    """At a block pass's 8 queries a grid step of the extend kernels takes
    the page as it is stored: every query row against all of its [PS*K, D]
    rows in ONE product, one softmax update and ONE product (PERF.md §6,
    PR 46). At a prefill chunk's 128 it keeps a pair of products a KV head,
    128 x G rows tall: the MXU's fill a head at a time. One kernel, the
    form read from the shapes alone."""
    kernel, operands = _extend_operands(queries, quantized)
    jaxpr = jax.make_jaxpr(functools.partial(kernel, interpret=True, block=4)
                           )(*operands).jaxpr
    (call,) = [e for e in _equations(jaxpr) if e.primitive.name == "pallas_call"]
    products = [tuple(v.aval.shape for v in e.invars)
                for e in _equations(call.params["jaxpr"], True)
                if e.primitive.name == "dot_general"]
    heads, cells = EXTEND_KV * EXTEND_GROUPS, EXTEND_PAGE * EXTEND_KV
    if queries == 8:
        assert products == [
            ((queries * heads, 128), (cells, 128)),  # q, the page's keys
            ((queries * heads, cells), (cells, 128)),  # weights, its values
        ]
    else:
        rows = queries * EXTEND_GROUPS
        assert products == [
            ((rows, 128), (EXTEND_PAGE, 128)),  # a head's q, its keys
            ((rows, EXTEND_PAGE), (EXTEND_PAGE, 128)),  # weights, values
        ] * EXTEND_KV


# (q block, query heads, KV heads, page) -> the form of a grid step: the
# cells' calls, then the threshold's two sides at each cell's heads
EXTEND_BODIES = {
    (8, 32, 4, 128): "page",  # a block pass of sdar-30b-a3b: 2 blocks of 4
    (4, 32, 4, 128): "page",  # … and one block
    (8, 32, 8, 128): "page",  # a verify chunk of k + 1 <= 8 at Mistral-7B's
    (8, 32, 2, 128): "page",  # … and at Nemotron-3-Nano's heads
    (128, 32, 4, 128): "heads",  # a prefill chunk's q block
    (128, 32, 8, 128): "heads",
    (128, 32, 2, 128): "heads",
    (128, 4, 1, 16): "page",  # a debug preset's chunk: 32 KB of scores
    (32, 32, 4, 128): "page",  # the measured crossover at 4 KV heads …
    (64, 32, 4, 128): "heads",
    (16, 32, 8, 128): "page",  # … at 8 …
    (32, 32, 8, 128): "heads",
    (64, 32, 2, 128): "page",  # … and at 2: 2 MB of scores a step
    (8, 32, 32, 128): "heads",  # 32 ungrouped heads: 4 MB of scores
    (1, 4, 2, 8): "page",
}


@pytest.mark.parametrize("shape", sorted(EXTEND_BODIES), ids=lambda s: "-".join(
    map(str, s)))
def test_the_extend_body_is_a_function_of_the_shapes(shape):
    """`extend_body` answers from (q block, heads, KV heads, page size) and
    nothing else: no flag, no environment, no model's name — and a wrapper
    has no keyword that names a form."""
    assert pallas_attention.extend_body(*shape) == EXTEND_BODIES[shape]
    assert list(inspect.signature(pallas_attention.extend_body).parameters
                ) == ["blk_q", "heads", "num_kv", "page_size"]
    for wrapper in (pallas_attention.paged_flash_extend,
                    pallas_attention.paged_flash_extend_quant):
        assert "body" not in inspect.signature(wrapper).parameters
    source = inspect.getsource(pallas_attention.extend_body)
    assert "environ" not in source and "getenv" not in source


# --- prefill, extend and verify: the pool is the layer scan's carry ----------

# one dense layer and two expert layers: two LayerGroups, two scans
LATENT_TINY = get_preset("debug-mla-tiny")
CHUNK = 2 * PAGE_SIZE
ENTRY_POINTS = ("prefill_into_pages", "prefill_extend_pages",
                "verify_step_paged")
# (family, quantized, attention route): the families with an int8 pool have
# a Pallas extend kernel too; the latent family extends over a gather
SCANNED = [(name, quantized, route) for name in sorted(FAMILIES)
           for quantized in (False, True) for route in ("pallas", "xla")]
SCANNED.append(("deepseek_v3", False, "xla"))
# two double layers: a LayerGroup a sub-layer, four scans, and the branch a
# sub-layer 0 leaves for its sub-layer 1 in their carry
SCANNED.append(("longcat_flash", False, "xla"))
# (family, configuration, scans, values a scan may carry beside the pools:
# x, and in the scan of a sub-layer that LEAVES a branch the branch — the
# sub-layer that joins it passes it on unchanged, a constant of its scan)
LATENT = {"deepseek_v3": (deepseek_v3, LATENT_TINY, 2, {1}),
          "longcat_flash": (longcat_flash, get_preset("debug-longcat-tiny"),
                            4, {1, 2})}


def _scanned_jaxpr(family, cfg, entry, quantized, route, monkeypatch,
                   batch=ROWS):
    monkeypatch.setenv("LLMLB_TPU_ATTENTION", route)  # read while tracing
    fn = getattr(family, entry)
    fn._clear_cache()
    # a config of its own for each route: a static argument that no trace
    # made under the other route has seen
    cfg = dataclasses.replace(
        cfg, max_position_embeddings=cfg.max_position_embeddings
        + ("pallas", "xla").index(route) + 1)
    params = jax.eval_shape(lambda key: family.init_params(cfg, key),
                            jax.random.PRNGKey(0))
    pools = jax.eval_shape(
        lambda: family.init_kv_pages(cfg, PAGES, PAGE_SIZE,
                                     quantized=quantized))
    ids = jax.ShapeDtypeStruct((batch, CHUNK), jnp.int32)
    rows = jax.ShapeDtypeStruct((batch,), jnp.int32)
    tables = jax.ShapeDtypeStruct((batch, PAGES_PER_ROW), jnp.int32)
    if entry == "prefill_into_pages":
        args, kw = (ids, rows, tables), {}
    else:
        args = (ids, rows, rows, tables)
        kw = {"window": CHUNK} if entry == "verify_step_paged" else {}
    try:
        closed = jax.make_jaxpr(
            lambda p, a, ck, cv: fn(p, cfg, *a, ck, cv, **kw)
        )(params, args, *pools)
    finally:
        fn._clear_cache()  # traced with the env set
    return closed.jaxpr, pools


@pytest.mark.parametrize("entry", ENTRY_POINTS)
@pytest.mark.parametrize(
    "name,quantized,route", SCANNED,
    ids=[f"{n}-{'int8' if q else 'bf16'}-{r}" for n, q, r in SCANNED])
def test_prefill_and_extend_carry_the_pool_through_the_layer_scan(
        name, quantized, route, entry, monkeypatch):
    """No scan has a piece of the pool among its `xs` or `ys`, no equation
    yields one layer of it, none but the scatters (and the loops and calls
    they sit in) yields a pool, and an extend kernel is handed the pool
    whole. (At the parent of PR 32 each pool was a scan's `xs` and `ys`, and
    a stack of two groups was sliced per group and concatenated again.)"""
    family, cfg, groups, carried = (LATENT[name] if name in LATENT
                                    else (*FAMILIES[name], 1, {1}))
    jaxpr, pools = _scanned_jaxpr(family, cfg, entry, quantized, route,
                                  monkeypatch)
    stacked = {leaf.shape for leaf in jax.tree.leaves(pools)}
    a_layer = {shape[1:] for shape in stacked}
    values = {v.shape for v in jax.tree.leaves(
        jax.tree.map(llama.kv_pool_values, pools,
                     is_leaf=lambda p: isinstance(p, dict)))}
    # the Pallas route hands an int8 pool's scales to the kernel as the
    # layer's slice, as decode does; the values never
    may_slice = ({s[1:] for s in stacked - values}
                 if quantized and route == "pallas" else set())

    def shapes(variables):
        return [getattr(v.aval, "shape", None) for v in variables]

    eqns = list(_equations(jaxpr))
    scans = [eqn for eqn in eqns if eqn.primitive.name == "scan"]
    assert len(scans) == groups
    # x and the pools, and only where a group has a deferred branch
    # (llama.LayerGroup) the value it leaves for a later layer
    assert {eqn.params["num_carry"] - len(jax.tree.leaves(pools))
            for eqn in scans} == carried
    for eqn in scans:
        consts, carry = eqn.params["num_consts"], eqn.params["num_carry"]
        through = (shapes(eqn.invars[consts + carry:])
                   + shapes(eqn.outvars[carry:]))
        assert not [s for s in through if s and s[1:] in a_layer], through
        assert sorted(s for s in shapes(eqn.invars[consts:consts + carry])
                      if s in stacked) == sorted(
            leaf.shape for leaf in jax.tree.leaves(pools))

    sliced = [str(eqn) for eqn in eqns
              if any(s in a_layer - may_slice for s in shapes(eqn.outvars))]
    assert not sliced, f"a per-layer slice of the pool: {sliced}"
    makes_a_pool = {"scatter", "scan", "while", "pjit", "jit", "closed_call",
                    "core_call", "custom_jvp_call", "custom_vjp_call"}
    ranks = {shape[1:]: len(shape) for shape in stacked}
    copied = [str(eqn) for eqn in eqns
              if eqn.primitive.name not in makes_a_pool
              and any(s and ranks.get(s[1:]) == len(s)
                      and s[1:] not in may_slice
                      for s in shapes(eqn.outvars))]
    assert not copied, f"a pool or a group's part of one is built: {copied}"

    kernels = [eqn for eqn in eqns if eqn.primitive.name == "pallas_call"
               and "grouped_expert_matmul" not in str(
                   eqn.params.get("name_and_src_info",
                                  eqn.params.get("name")))]
    # (a fresh prompt's flash_prefill reads no pool)
    handed = [[s for s in shapes(eqn.invars) if s and len(s) >= 3]
              for eqn in kernels]
    assert not [s for ops in handed for s in ops
                if s in a_layer - may_slice], handed
    # a bf16 pool may reach the kernel under the view [L, P, PS*K, D], a
    # page as the rows it is stored as (`extend_body` "page": this chunk's
    # scores are small), which is the whole pool still
    as_rows = {(*s[:2], s[2] * s[3], s[4]): s for s in values
               if len(s) == 5}
    whole = [[as_rows.get(s, s) for s in ops] for ops in handed]
    readers = [ops for ops in whole if values & set(ops)]
    reads_the_pool = route == "pallas" and entry != "prefill_into_pages"
    assert len(readers) == (1 if reads_the_pool else 0)  # the scan's body
    for ops in readers:
        assert sorted(s for s in ops if s in values) == sorted(
            v.shape for v in jax.tree.leaves(pools) if v.shape in values)


# --- the logits' offset (a block family's pass) at its default ----------------

OFFSET_FAMILIES = {**FAMILIES,
                   **{name: case[:2] for name, case in LATENT.items()}}


def test_a_decode_step_mixes_its_rows_in_place_and_a_prefill_sorts(
        monkeypatch):
    """A decode step of a mixture family holds no sort of its S x k
    assignments and ONE `grouped_expert_matmul` a mixture (the rows
    multiplied where they stand: ops/pallas_moe.expert_rows_in_place, PR
    63); a prefill of more rows than `pallas_moe.ROWS_IN_PLACE` keeps the
    sorted route: the sort and its three products, in its layer scan."""
    family, cfg = FAMILIES["mixtral"]
    decode = list(_equations(_decode_jaxpr(family, cfg, False, monkeypatch)))
    assert sum(_is_expert_kernel(eqn) for eqn in decode
               if eqn.primitive.name == "pallas_call") == LAYERS
    assert not [eqn for eqn in decode if eqn.primitive.name == "sort"]
    batch = pallas_moe.ROWS_IN_PLACE // CHUNK + 1
    prefill = list(_equations(_scanned_jaxpr(
        family, cfg, "prefill_into_pages", False, "pallas", monkeypatch,
        batch=batch)[0]))
    assert sum(_is_expert_kernel(eqn) for eqn in prefill
               if eqn.primitive.name == "pallas_call") == 3
    keys = batch * CHUNK * cfg.experts_per_token
    assert [eqn.invars[0].aval.shape for eqn in prefill
            if eqn.primitive.name == "sort"] == [(keys,)]


@pytest.mark.parametrize("entry", ENTRY_POINTS[1:])
@pytest.mark.parametrize("name", sorted(OFFSET_FAMILIES))
def test_the_logits_offset_at_its_default_lowers_the_program_that_was(
        name, entry, monkeypatch):
    """llama._prefill_extend_paged_impl's `logits_from` / `logits_len` (a
    block family's pass sends two blocks and wants one's logits) are named
    by no other family, and named at their defaults they lower an extend
    and a verify program to the text they lower to unnamed; set, they lower
    another program."""
    family, cfg = OFFSET_FAMILIES[name]
    monkeypatch.setenv("LLMLB_TPU_ATTENTION", "xla")  # read while tracing
    body = llama._prefill_extend_paged_impl
    fn = getattr(family, entry)
    params = jax.eval_shape(lambda key: family.init_params(cfg, key),
                            jax.random.PRNGKey(0))
    pools = jax.eval_shape(lambda: family.init_kv_pages(cfg, PAGES, PAGE_SIZE))
    ids = jax.ShapeDtypeStruct((ROWS, CHUNK), jnp.int32)
    rows = jax.ShapeDtypeStruct((ROWS,), jnp.int32)
    tables = jax.ShapeDtypeStruct((ROWS, PAGES_PER_ROW), jnp.int32)
    kw = {"window": CHUNK} if entry == "verify_step_paged" else {}

    def lowered(**named):
        seen = []

        def spy(*args, **kwargs):
            seen.append(kwargs)
            return body(*args, **kwargs, **named)

        monkeypatch.setattr(family, "_prefill_extend_paged_impl", spy)
        # the entry's own function under a jit of this call's: no trace of
        # an earlier one is found again
        return jax.jit(
            lambda p, i, r, t, ck, cv: fn.__wrapped__(
                p, cfg, i, r, r, t, ck, cv, **kw)
        ).lower(params, ids, rows, tables, *pools).as_text(), seen

    plain, seen = lowered()
    assert seen and not [k for kwargs in seen for k in kwargs
                         if k.startswith("logits_")]
    assert lowered(logits_from=None, logits_len=None)[0] == plain
    if entry == "verify_step_paged":
        half = lowered(logits_from=jnp.zeros((ROWS,), jnp.int32),
                       logits_len=CHUNK // 2)[0]
        assert half != plain
        assert f"{ROWS}x{CHUNK // 2}x{cfg.vocab_size}xf32" in half
        assert f"{ROWS}x{CHUNK // 2}x{cfg.vocab_size}xf32" not in plain


# --- the same programs as the chip's compiler leaves them --------------------
#
# The TPU compiler is installed here and compiles for a chip that is described,
# not attached. Only the test that needs it describes the topology (one process
# at a time may load libtpu), so the call lives in a fixture of this file.

CHIP_PAGES, CHIP_PAGE_SIZE, CHIP_ROWS, CHIP_TABLE = 400, 128, 32, 16
CHIP_CFG = llama.LlamaConfig(  # Mistral-7B's widths, two layers deep
    vocab_size=32000, hidden_size=4096, intermediate_size=14336,
    num_layers=2, num_heads=32, num_kv_heads=8, rope_theta=1e6,
    tie_word_embeddings=False)


def _on_chip(sharding, tree):
    """`tree`'s shapes as they lie on the described chip."""
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=sharding), tree)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled_burst(one_chip, monkeypatch, family, cfg, *, pages, rows,
                    window, kernels, pool=None):
    """Two decode steps of `family` under a scan, as the engine's burst
    program runs them, compiled for the described v5e at `pages` of the
    cell's page size, `rows` rows and a context `window`. `kernels`: the
    jitted functions under the family's `decode_step_paged`, traced before
    with the interpreter or the XLA path. `pool`: keywords of the family's
    `init_kv_pages` (`quantized`; `num_slots` for a state a slot)."""
    monkeypatch.setenv("LLMLB_TPU_ATTENTION", "pallas")
    # the backend in this process is the CPU; the program under test is the
    # chip's, so the kernels lower through Mosaic
    for module in (pallas_attention, pallas_moe, ssm):
        monkeypatch.setattr(module, "_interpret_default", lambda: False)
    jitted = (family.decode_step_paged, *kernels)
    for fn in jitted:
        fn._clear_cache()
    on_chip = functools.partial(_on_chip, one_chip)
    params = on_chip(jax.eval_shape(
        lambda key: family.init_params(cfg, key), jax.random.PRNGKey(0)))
    cache_k, cache_v = on_chip(jax.eval_shape(
        lambda: family.init_kv_pages(cfg, pages, CHIP_PAGE_SIZE,
                                     **(pool or {}))))
    ints = on_chip(jax.ShapeDtypeStruct((rows,), jnp.int32))
    live = on_chip(jax.ShapeDtypeStruct((rows,), jnp.bool_))
    tables = on_chip(jax.ShapeDtypeStruct((rows, CHIP_TABLE), jnp.int32))

    def burst(params, last, lens, cache_k, cache_v, tables, live):
        def body(carry, _):
            last, lens, ck, cv = carry
            logits, ck, cv, *counters = family.decode_step_paged(
                params, cfg, last, lens, ck, cv, tables, window=window,
                live=live)
            return (jnp.argmax(logits, -1).astype(jnp.int32), lens + 1,
                    ck, cv), counters

        return jax.lax.scan(body, (last, lens, cache_k, cache_v), None,
                            length=2)

    try:
        # conftest.py asks for float32 matmuls; the chip's program has bf16
        # operands, and Mosaic refuses a float32 contraction over them
        with jax.default_matmul_precision("default"):
            return jax.jit(burst, donate_argnums=(3, 4)).lower(
                params, ints, ints, cache_k, cache_v, tables, live).compile()
    finally:
        for fn in jitted:
            fn._clear_cache()


def _coefficient_rows(hlo, results):
    """What PR 56 took out of a compiled burst with state-space layers at 64
    heads of 64: the `[slots, 8, H P]` buffer of coefficient rows (zeros and
    two rows of numbers, written in front of every state step and read back
    by it) under any op, and any operand of a state step that a
    `dynamic-update-slice` (or a `pad`) made."""
    found = [(shape, op) for shape, op in results
             if shape.startswith(f"f32[{CHIP_ROWS},8,4096]")]
    made_by = dict(re.findall(r"^\s*(?:ROOT )?(%\S+) = \S+ ([\w\-]+)\(", hlo,
                              re.M))
    steps = re.findall(r"custom-call\(([^)]*)\)[^\n]*ssm_decode_step", hlo)
    operands = [name for step in steps
                for name in re.findall(r"%[\w.\-]+", step)]
    return found + [(name, made_by[name]) for name in operands
                    if made_by.get(name) in ("dynamic-update-slice", "pad")]


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_compiled_decode_burst_copies_no_part_of_the_pool(quantized, one_chip,
                                                          monkeypatch):
    """Two decode steps under a scan, as the engine's burst program runs
    them, compiled for a v5e at the benchmark cell's pool and widths: the
    compiler materializes no per-layer piece of the value pool and copies no
    value pool whole. (At the parent of PR 25 this program held a 105 MB
    `bf16[400,128,8,128]` fusion per layer for K and for V.)"""
    hlo = _compiled_burst(
        one_chip, monkeypatch, llama, CHIP_CFG, pages=CHIP_PAGES,
        rows=CHIP_ROWS, window=512, pool={"quantized": quantized},
        kernels=(pallas_attention.paged_flash_decode,
                 pallas_attention.paged_flash_decode_quant)).as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2  # a layer
    # the pool as it is stored or as a page's [PS*K, D] rows
    layer_values = r"(bf16|s8)\[400,(128,8|1024),128\]"
    whole_pool = r"(bf16|s8)\[2,400,(128,8|1024),128\]"  # the values; an int8
    # pool's scales are re-laid-out at the loop's ends at either commit
    results = re.findall(r"^\s*(?:ROOT )?%\S+ = (\S+) ([\w\-]+)\(", hlo, re.M)
    assert results
    bad = [(shape, op) for shape, op in results
           if re.match(layer_values, shape)
           or (op == "copy" and re.match(whole_pool, shape))]
    assert not bad, bad


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_compiled_admitting_burst_copies_no_part_of_the_pool(quantized,
                                                             one_chip,
                                                             monkeypatch):
    """The burst whose first step carries an arrival's prompt
    (StepPrograms.admit_many: the mixed step, then the scan of the decode
    steps left), compiled for a v5e at the benchmark cell's pool, widths and
    prompt width of 128: what the pure decode burst above is held to — no
    per-layer piece of the value pool, no value pool copied whole — and the
    kernels a layer that it needs: the decode kernel in the mixed step and
    in the scan's body, the prefill kernel in the mixed step alone."""
    from llmlb_tpu.engine.programs import StepPrograms

    monkeypatch.setenv("LLMLB_TPU_ATTENTION", "pallas")
    for module in (pallas_attention, pallas_moe, ssm):
        monkeypatch.setattr(module, "_interpret_default", lambda: False)
    jitted = (llama.decode_step_paged, llama.mixed_step_paged,
              pallas_attention.paged_flash_decode,
              pallas_attention.paged_flash_decode_quant,
              pallas_attention.flash_prefill)
    for fn in jitted:
        fn._clear_cache()
    programs = StepPrograms(llama, CHIP_CFG, None, decode_burst=3,
                            max_draft_tokens=1, num_slots=CHIP_ROWS,
                            slot_capacity=CHIP_TABLE * CHIP_PAGE_SIZE,
                            eos_id=2)
    on_chip = functools.partial(_on_chip, one_chip)
    params = on_chip(jax.eval_shape(
        lambda key: llama.init_params(CHIP_CFG, key), jax.random.PRNGKey(0)))
    cache_k, cache_v = on_chip(jax.eval_shape(
        lambda: llama.init_kv_pages(CHIP_CFG, CHIP_PAGES, CHIP_PAGE_SIZE,
                                    quantized=quantized)))

    def vector(dtype, *shape):
        return on_chip(jax.ShapeDtypeStruct(shape or (CHIP_ROWS,), dtype))

    ints, floats = vector(jnp.int32), vector(jnp.float32)
    key = on_chip(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    try:
        with jax.default_matmul_precision("default"):
            hlo = programs._build_admit_many(3, 512).lower(
                params, ints, ints, cache_k, cache_v,
                vector(jnp.int32, CHIP_ROWS, CHIP_TABLE), floats, floats,
                ints, ints, key, vector(jnp.bool_),
                vector(jnp.int32, 1, 128), vector(jnp.int32, 4),
                vector(jnp.float32, 2)).compile().as_text()
    finally:
        for fn in jitted:
            fn._clear_cache()
    # a layer: decode and prefill in the mixed step, decode in the scan
    assert hlo.count('custom_call_target="tpu_custom_call"') == 6
    layer_values = r"(bf16|s8)\[400,(128,8|1024),128\]"
    whole_pool = r"(bf16|s8)\[2,400,(128,8|1024),128\]"
    results = re.findall(r"^\s*(?:ROOT )?%\S+ = (\S+) ([\w\-]+)\(", hlo, re.M)
    assert results
    bad = [(shape, op) for shape, op in results
           if re.match(layer_values, shape)
           or (op == "copy" and re.match(whole_pool, shape))]
    assert not bad, bad


def test_a_pool_at_heads_of_64_is_held_two_heads_to_a_row_and_copied_nowhere(
        one_chip, monkeypatch):
    """Granite-4.0-H's widths (8 KV heads of 64, 64 state-space heads of 64
    at ONE group) at a cut of three layers, compiled for a v5e: the page
    pool is `[A, P, 128, 4, 128]`, two heads to a 128-lane row, so no tile of
    it is half padding, and the compiler neither re-lays it out nor copies
    it (as `[.., 8, 64]` at the whole model's size it did both inside every
    decode step, with 1.2 GB of temporaries: PERF.md section 6, PR 55); the
    state step at one group of 4,096 channels lowers through Mosaic."""
    from llmlb_tpu.models import granite_hybrid

    cfg = granite_hybrid.GraniteHybridConfig(
        vocab_size=100352, hidden_size=2048, intermediate_size=8192,
        num_layers=3, num_heads=32, num_kv_heads=8, tie_word_embeddings=True,
        embedding_multiplier=12.0, logits_scaling=8.0,
        layer_types=("mamba", "attention", "mamba"))
    assert cfg.pool_pack == 2
    compiled = _compiled_burst(
        one_chip, monkeypatch, granite_hybrid, cfg, pages=CHIP_PAGES,
        rows=CHIP_ROWS, window=512, pool={"num_slots": CHIP_ROWS},
        kernels=(pallas_attention.paged_flash_decode, ssm.ssm_decode_step))
    hlo = compiled.as_text()
    # two state steps and one attention a decode step
    assert hlo.count('custom_call_target="tpu_custom_call"') == 3
    pool = rf"bf16\[1,{CHIP_PAGES},(128,4|512),128\]"
    results = re.findall(r"^\s*(?:ROOT )?%\S+ = (\S+) ([\w\-]+)\(", hlo, re.M)
    bad = [(shape, op) for shape, op in results
           if re.match(pool, shape) and op in ("copy", "transpose")]
    assert not bad, bad
    assert "remat_compressed" not in hlo
    assert not _coefficient_rows(hlo, results)
    # the head is the embedding table as it lies: no transposed copy of it
    assert not [shape for shape, op in results
                if shape.startswith("bf16[2048,100352]")]
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_a_packed_pool_that_rotates_and_two_carried_rows_are_copied_nowhere(
        one_chip, monkeypatch):
    """LFM2-24B-A2B's widths (8 rotated KV heads of 64, gated short
    convolutions over 2,048 channels, 64 experts of 1,536 held whole) at a
    cut of three layers, compiled for a v5e: the page pool is `[A, P, 128,
    4, 128]`, two heads to a 128-lane row, and the compiler neither re-lays
    it out nor copies it; the stacked experts are read where they lie by
    the grouped products at their new shape, `[2048, 1536]` a matrix; the
    carried rows `[C, slots, 2, 2048]` (8 KB a slot and layer) cost no
    temporary of any size."""
    from llmlb_tpu.models import lfm2_moe

    cfg = lfm2_moe.Lfm2MoeConfig(
        vocab_size=65536, hidden_size=2048, intermediate_size=11776,
        num_layers=3, num_heads=32, num_kv_heads=8, rope_theta=1e6,
        tie_word_embeddings=True, layer_types=("conv", "full_attention",
                                               "conv"),
        num_dense_layers=1, num_experts=64, experts_per_token=4,
        moe_intermediate_size=1536)
    assert cfg.pool_pack == 2 and cfg.num_moe_layers == 2
    compiled = _compiled_burst(
        one_chip, monkeypatch, lfm2_moe, cfg, pages=CHIP_PAGES,
        rows=CHIP_ROWS, window=512, pool={"num_slots": CHIP_ROWS},
        kernels=(pallas_attention.paged_flash_decode,
                 pallas_moe.grouped_expert_matmul,
                 pallas_moe.expert_rows_in_place))
    hlo = compiled.as_text()
    # one attention and two mixtures, a kernel each, a decode step
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1 + 2
    pool = rf"bf16\[1,{CHIP_PAGES},(128,4|512),128\]"
    experts = r"bf16\[(2,)?64,(2048,1536|1536,2048)\]"
    results = re.findall(r"^\s*(?:ROOT )?%\S+ = (\S+) ([\w\-]+)\(", hlo, re.M)
    moves_nothing = ("parameter", "get-tuple-element", "tuple", "bitcast")
    bad = [(shape, op) for shape, op in results
           if (re.match(pool, shape) and op in ("copy", "transpose"))
           or (re.match(experts, shape) and op not in moves_nothing)]
    assert not bad, bad
    assert "remat_compressed" not in hlo
    # the head is the embedding table as it lies: no transposed copy of it
    assert not [shape for shape, op in results
                if shape.startswith("bf16[2048,65536]")]
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_a_state_beside_a_latent_pool_is_stepped_in_place_and_copied_nowhere(
        one_chip, monkeypatch):
    """Kimi-Linear-48B-A3B's widths (32 KDA heads of 128 x 128 float32, a
    decay a key channel; latent attention at 512 + 64 without rotary; 16 of
    256 experts of 1,024 held) at a cut of three layers (K dense, K, A),
    compiled for a v5e: the state `[K, slots, 128, 4096]` float32 goes
    through the `kda_step` kernel aliased in and out and is never copied or
    re-laid; the two latent pools are written by a scatter and read where
    they lie; the stacked experts are read in place by the grouped
    products; the step's temporaries stay small."""
    from llmlb_tpu.models import kimi_linear
    from llmlb_tpu.ops import delta_rule

    cfg = kimi_linear.KimiLinearConfig(
        vocab_size=163840, hidden_size=2304, intermediate_size=9216,
        num_layers=3, num_heads=32, num_kv_heads=32, head_dim=64,
        rms_eps=1e-5, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, num_experts=16,
        router_experts=256, experts_per_token=8, moe_intermediate_size=1024,
        num_shared_experts=1, first_k_dense=1, routed_scaling_factor=2.446,
        mixers=("kda", "kda", "mla"), kda_heads=32, kda_head_dim=128)
    assert [kind for _, kind, _ in kimi_linear.runs(cfg)] == [
        "kda_dense", "kda_moe", "mla_moe"]
    compiled = _compiled_burst(
        one_chip, monkeypatch, kimi_linear, cfg, pages=CHIP_PAGES,
        rows=CHIP_ROWS, window=512, pool={"num_slots": CHIP_ROWS},
        kernels=(delta_rule.delta_rule_decode_step,
                 pallas_attention.paged_latent_decode,
                 pallas_moe.grouped_expert_matmul,
                 pallas_moe.expert_rows_in_place))
    hlo = compiled.as_text()
    # two rule steps, one latent attention, two mixtures of a kernel each
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2 + 1 + 2
    assert hlo.count("kda_step") >= 2  # under the vector decay's own name
    state = rf"f32\[2,{CHIP_ROWS},128,4096\]"
    pools = rf"bf16\[1,{CHIP_PAGES},128,(512|128)\]"
    experts = r"bf16\[(1,)?16,(2304,1024|1024,2304)\]"
    results = re.findall(r"^\s*(?:ROOT )?%\S+ = (\S+) ([\w\-]+)\(", hlo, re.M)
    moves_nothing = ("parameter", "get-tuple-element", "tuple", "bitcast")
    bad = [(shape, op) for shape, op in results
           if (re.match(state, shape) and op in ("copy", "transpose"))
           or (re.match(pools, shape) and op in ("copy", "transpose"))
           or (re.match(experts, shape) and op not in moves_nothing)]
    assert not bad, bad
    assert "remat_compressed" not in hlo
    assert compiled.memory_analysis().temp_size_in_bytes < 96 << 20


def test_pages_with_index_keys_and_a_latent_ring_are_read_where_they_lie(
        one_chip, monkeypatch):
    """dots3-note-prev's widths (a full layer's 128 heads on a latent of 512
    with 64 index heads of 128 and a top-k of 2,048; a sliding layer's 64
    heads on a latent of 1,024 over 513 positions; 32 of 256 experts of
    1,536 held) at a cut of three layers (F dense, F, S), compiled for a
    v5e: Mosaic takes the score kernel, the sparse decode kernel and the
    latent kernel over a ring of 640 cells; the two page pools — the second
    a row of 256 lanes, the index key behind the rope's tile — and the
    rings are written by scatters and read where they lie, never copied or
    re-laid; the step's temporaries stay small."""
    from llmlb_tpu.models import dots3_note

    cfg = dots3_note.Dots3NoteConfig(
        vocab_size=19008, hidden_size=5120, intermediate_size=13824,
        num_layers=3, num_heads=128, num_kv_heads=128, head_dim=64,
        rope_theta=8e7, rms_eps=1e-5, kv_lora_rank=512, q_lora_rank=1024,
        q_lora_scale=5 ** 0.5, kv_lora_scale=10 ** 0.5,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        num_experts=32, router_experts=256, experts_per_token=8,
        moe_intermediate_size=1536, num_shared_experts=1, first_k_dense=1,
        routed_scaling_factor=1.0,
        layer_types=("full", "full", "sliding"))
    assert [kind for _, kind, _ in dots3_note.runs(cfg)] == [
        "full_dense", "full_moe", "sliding_moe"]
    compiled = _compiled_burst(
        one_chip, monkeypatch, dots3_note, cfg, pages=CHIP_PAGES,
        rows=CHIP_ROWS, window=CHIP_TABLE * CHIP_PAGE_SIZE,
        pool={"num_slots": CHIP_ROWS},
        kernels=(pallas_attention.index_scores_decode,
                 pallas_attention.sparse_latent_decode,
                 pallas_attention.paged_latent_decode,
                 pallas_moe.grouped_expert_matmul,
                 pallas_moe.expert_rows_in_place))
    hlo = compiled.as_text()
    for kernel, calls in (("index_scores_decode", 2),
                          ("sparse_latent_decode", 2),
                          ("window_latent_decode", 1)):
        assert len(re.findall(rf'custom_call_target="tpu_custom_call"[^\n]*'
                              rf'{kernel}|{kernel}[^\n]*tpu_custom_call',
                              hlo)) >= calls, kernel
    pools = rf"bf16\[2,{CHIP_PAGES},128,(512|256)\]"
    rings = rf"bf16\[1,{CHIP_ROWS + 1},640,(1024|128)\]"
    experts = r"bf16\[(1,)?32,(5120,1536|1536,5120)\]"
    results = re.findall(r"^\s*(?:ROOT )?%\S+ = (\S+) ([\w\-]+)\(", hlo, re.M)
    moves_nothing = ("parameter", "get-tuple-element", "tuple", "bitcast")
    bad = [(shape, op) for shape, op in results
           if (re.match(pools, shape) and op in ("copy", "transpose"))
           or (re.match(rings, shape) and op in ("copy", "transpose"))
           or (re.match(experts, shape) and op not in moves_nothing)]
    assert not bad, bad
    assert "remat_compressed" not in hlo
    assert compiled.memory_analysis().temp_size_in_bytes < 96 << 20


def test_a_chunk_under_a_selection_keeps_its_scores_on_the_chip(
        one_chip, monkeypatch):
    """dots3-note-prev's widths at the same cut (F dense, F, S), an extend
    chunk of 512 queries over a table of 16 pages, compiled for a v5e: each
    full layer's attention under the selection is ONE Mosaic call named
    `sparse_latent_extend`, and nothing of heads x queries x cells in
    float32 is left in HBM under that scope (the blocked einsums held
    [1, 128, 512, 1024] there, 268 MB, and rewrote it five times a block:
    PERF.md §6, PR 65). The dispatcher says which route it took: the Pallas
    call here, "xla" on the CPU (tests/ops/test_sparse_attention.py)."""
    from llmlb_tpu.models import dots3_note
    from llmlb_tpu.ops import attention

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent
                           / "scripts"))
    import program_temporaries

    cfg = dots3_note.Dots3NoteConfig(
        vocab_size=19008, hidden_size=5120, intermediate_size=13824,
        num_layers=3, num_heads=128, num_kv_heads=128, head_dim=64,
        rope_theta=8e7, rms_eps=1e-5, kv_lora_rank=512, q_lora_rank=1024,
        q_lora_scale=5 ** 0.5, kv_lora_scale=10 ** 0.5,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        num_experts=32, router_experts=256, experts_per_token=8,
        moe_intermediate_size=1536, num_shared_experts=1, first_k_dense=1,
        routed_scaling_factor=1.0,
        layer_types=("full", "full", "sliding"))
    monkeypatch.setenv("LLMLB_TPU_ATTENTION", "pallas")
    for module in (pallas_attention, pallas_moe):
        monkeypatch.setattr(module, "_interpret_default", lambda: False)
    fn = dots3_note.prefill_extend_pages
    jitted = (fn, pallas_attention.sparse_latent_extend,
              pallas_moe.grouped_expert_matmul,
              pallas_moe.expert_rows_in_place)
    for f in jitted:
        f._clear_cache()
    on_chip = functools.partial(_on_chip, one_chip)
    params = on_chip(jax.eval_shape(
        lambda key: dots3_note.init_params(cfg, key), jax.random.PRNGKey(0)))
    pools = on_chip(jax.eval_shape(
        lambda: dots3_note.init_kv_pages(cfg, CHIP_PAGES, CHIP_PAGE_SIZE,
                                         num_slots=1)))
    ids = on_chip(jax.ShapeDtypeStruct((1, 512), jnp.int32))
    row = on_chip(jax.ShapeDtypeStruct((1,), jnp.int32))
    tables = on_chip(jax.ShapeDtypeStruct((1, CHIP_TABLE), jnp.int32))
    attention._traced.pop("sparse_latent_extend", None)
    try:
        with jax.default_matmul_precision("default"):
            compiled = fn.lower(params, cfg, ids, row, row, tables,
                                *pools).compile()
    finally:
        for f in jitted:
            f._clear_cache()
    assert (attention.traced_routes()["sparse_latent_extend"]
            == "pallas:sparse_latent_extend")
    hlo = compiled.as_text()
    calls = re.findall(r'^[^\n]*custom_call_target="tpu_custom_call"[^\n]*$',
                       hlo, re.M)
    assert sum("sparse_latent_extend/pallas_call" in c for c in calls) == 2
    scores = 128 * 512 * CHIP_PAGE_SIZE * 4  # one page's, in float32
    held = [r for r in program_temporaries.large_results(hlo, scores)
            if r["shape"].startswith("f32")
            and "sparse_latent_extend" in r["op_name"]]
    assert not held, held
    pool = rf"bf16\[2,{CHIP_PAGES},128,(512|256)\]"
    results = re.findall(r"^\s*(?:ROOT )?%\S+ = (\S+) ([\w\-]+)\(", hlo, re.M)
    bad = [(shape, op) for shape, op in results
           if re.match(pool, shape) and op in ("copy", "transpose")]
    assert not bad, bad


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("kv_heads,groups", [(8, 4), (2, 16), (32, 1)],
                         ids=["mistral-K8xG4", "nemotron-K2xG16", "MHA-32"])
def test_the_decode_kernels_lower_through_mosaic_at_the_cells_heads(
        kv_heads, groups, quantized, one_chip):
    """One masked product over a page's [PS*K, D] rows, compiled by the
    chip's own compiler at the heads the benchmark's dense cells serve
    (Mistral-7B's 8 x 4, Nemotron-3-Nano's 2 x 16 attention layers) and at
    32 ungrouped heads, where a grid step's scores are 512 KB: the reshape of
    the page block, the mask and the products are forms Mosaic takes, and
    the scratch fits its VMEM. The bf16 pool reaches the kernel as it lies;
    an int8 pool of two KV heads is re-laid-out before it at either commit
    (XLA keeps `s8[.., 128, 2, 128]` cell-minor; PERF.md §7), so the int8
    cases only have to compile."""
    pool = jax.ShapeDtypeStruct(
        (2, CHIP_PAGES, CHIP_PAGE_SIZE, kv_heads, 128),
        jnp.int8 if quantized else jnp.bfloat16)
    scales = jax.ShapeDtypeStruct((CHIP_PAGES, CHIP_PAGE_SIZE, kv_heads),
                                  jnp.float32)
    q = jax.ShapeDtypeStruct((CHIP_ROWS, kv_heads * groups, 128),
                             jnp.bfloat16)
    tables = jax.ShapeDtypeStruct((CHIP_ROWS, CHIP_TABLE), jnp.int32)
    lens = jax.ShapeDtypeStruct((CHIP_ROWS,), jnp.int32)
    layer = jax.ShapeDtypeStruct((), jnp.int32)
    if quantized:
        kernel = pallas_attention.paged_flash_decode_quant
        operands = (q, pool, scales, pool, scales, layer, tables, lens)
    else:
        kernel = pallas_attention.paged_flash_decode
        operands = (q, pool, pool, layer, tables, lens)
    with jax.default_matmul_precision("default"):
        hlo = jax.jit(functools.partial(kernel, interpret=False)).lower(
            *_on_chip(one_chip, operands)).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1
    copied = re.findall(
        r"^\s*(?:ROOT )?%\S+ = (?:bf16|s8)\[2,400,\S+ (copy|fusion)\(", hlo,
        re.M)
    assert quantized or not copied, "the pool is re-laid-out for the kernel"


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("queries", [4, 8, 32, 64, 128])
@pytest.mark.parametrize("kv_heads,groups", [(4, 8), (8, 4), (2, 16)],
                         ids=["sdar-K4xG8", "mistral-K8xG4",
                              "nemotron-K2xG16"])
def test_the_extend_kernels_lower_through_mosaic_at_the_cells_heads(
        kv_heads, groups, queries, quantized, one_chip):
    """Either form of the extend kernels' grid step, compiled by the chip's
    own compiler at the heads of the cells that run it and at q blocks on
    both sides of the threshold: the page's [PS*K, D] view, the mask over
    rows of (position, head) and the products are forms Mosaic takes, the
    scores fit its VMEM, and the bf16 pool reaches the kernel as it lies —
    the view of a page as its rows is a bitcast, not a copy of the pool in
    front of the call (PR 45's first fault was of that kind, and visible
    here before any chip run). An int8 pool of few KV heads is re-laid-out
    before either form at either commit (PERF.md §7), so the int8 cases
    only have to compile."""
    pages = 400 if kv_heads != EXTEND_KV else EXTEND_PAGES
    kernel, operands = _extend_operands(queries, quantized, kv_heads, groups,
                                        CHIP_ROWS, 8, pages)
    with jax.default_matmul_precision("default"):
        hlo = jax.jit(functools.partial(
            kernel, interpret=False, block=4 if kv_heads == EXTEND_KV else 1)
        ).lower(*_on_chip(one_chip, operands)).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1
    moved = re.findall(
        rf"^\s*(?:ROOT )?%\S+ = (?:bf16|s8)\[2,{pages},\S+ "
        r"(copy|fusion|transpose)\(", hlo, re.M)
    assert quantized or not moved, "the pool is re-laid-out for the kernel"


# --- prefill and extend, as the chip's compiler leaves them ------------------

LATENT_PAGES, LATENT_ROWS = 768, 64
LATENT_CFG = deepseek_v3.DeepseekV3Config(  # kanana-2-30b-a3b's widths: one
    # dense and one expert layer, so two groups and two scans
    vocab_size=128256, hidden_size=2048, intermediate_size=6144,
    num_layers=2, num_heads=32, num_kv_heads=32, head_dim=64,
    rope_theta=1e6, rms_eps=1e-6, max_position_embeddings=32768)
BLOCK_PAGES = 544
BLOCK_CFG = sdar_moe.SdarMoeConfig(  # sdar-30b-a3b's widths, two layers deep:
    # the block mask in the prefill and extend kernels, experts of width 768
    vocab_size=151936, hidden_size=2048, intermediate_size=6144,
    num_layers=2, num_heads=32, num_kv_heads=4, head_dim=128,
    rope_theta=1e6, rms_eps=1e-6, max_position_embeddings=32768)
COMPILED = {
    "sdar_moe-bf16": (sdar_moe, BLOCK_CFG, BLOCK_PAGES, False),
    "llama-bf16": (llama, CHIP_CFG, CHIP_PAGES, False),
    "llama-int8": (llama, CHIP_CFG, CHIP_PAGES, True),
    "deepseek_v3-bf16": (deepseek_v3, LATENT_CFG, LATENT_PAGES, False),
}


@pytest.mark.parametrize("entry", ENTRY_POINTS[:2])
@pytest.mark.parametrize("case", sorted(COMPILED))
def test_compiled_prefill_and_extend_copy_no_part_of_the_pool(
        case, entry, one_chip, monkeypatch):
    """A 128-token prompt prefilled, or appended to a row, at the benchmark
    cells' pools and widths, compiled for a v5e: the compiler copies no
    value pool, slices no layer out of one and writes none back, and the
    program's temporaries stay under one layer of the pool — the pool is
    updated where it lies. (At the parent of PR 32 the Mistral programs held
    two whole-pool copies and a `dynamic-slice` and `dynamic-update-slice`
    of each layer of K and of V: 646 MB of temporaries for a prompt that
    leaves 1 MB in two layers.)"""
    from llmlb_tpu.ops import pallas_moe

    family, cfg, pages, quantized = COMPILED[case]
    monkeypatch.setenv("LLMLB_TPU_ATTENTION", "pallas")
    monkeypatch.setattr(pallas_attention, "_interpret_default", lambda: False)
    monkeypatch.setattr(pallas_moe, "_interpret_default", lambda: False)
    fn = getattr(family, entry)
    jitted = (fn, pallas_attention.flash_prefill,
              pallas_attention.paged_flash_extend,
              pallas_attention.paged_flash_extend_quant,
              pallas_moe.grouped_expert_matmul,
              pallas_moe.expert_rows_in_place)
    for f in jitted:
        f._clear_cache()

    on_chip = functools.partial(_on_chip, one_chip)

    params = on_chip(jax.eval_shape(
        lambda key: family.init_params(cfg, key), jax.random.PRNGKey(0)))
    pools = on_chip(jax.eval_shape(
        lambda: family.init_kv_pages(cfg, pages, CHIP_PAGE_SIZE,
                                     quantized=quantized)))
    ids = on_chip(jax.ShapeDtypeStruct((1, CHIP_PAGE_SIZE), jnp.int32))
    row = on_chip(jax.ShapeDtypeStruct((1,), jnp.int32))
    tables = on_chip(jax.ShapeDtypeStruct((1, CHIP_TABLE), jnp.int32))
    args = ((ids, row, tables) if entry == "prefill_into_pages"
            else (ids, row, row, tables))
    try:
        with jax.default_matmul_precision("default"):
            compiled = fn.lower(params, cfg, *args, *pools).compile()
    finally:
        for f in jitted:
            f._clear_cache()

    hlo = compiled.as_text()
    values = [llama.kv_pool_values(pool).shape for pool in pools]
    rest = {",".join(map(str, shape[1:])) for shape in values}
    a_layer = rf"(bf16|s8)\[(1,)?({'|'.join(rest)})\]"
    whole_pool = rf"(bf16|s8)\[{cfg.num_layers},({'|'.join(rest)})\]"
    moved = ("copy", "copy-start", "copy-done", "dynamic-slice",
             "dynamic-update-slice", "concatenate")
    results = re.findall(r"^\s*(?:ROOT )?%\S+ = (\S+) ([\w\-]+)\(", hlo, re.M)
    assert results
    bad = [(shape, op) for shape, op in results
           if re.match(a_layer, shape)
           or (op in moved and re.match(whole_pool, shape))]
    assert not bad, bad

    layer_bytes = min(
        jnp.dtype(v.dtype).itemsize * v.size // cfg.num_layers
        for v in map(llama.kv_pool_values, pools))
    budget = layer_bytes
    if quantized:
        # An int8 pool's scales f32[.., PS, K] lie PS-minor in HBM; the
        # scatter and the kernel want one layer of them K-minor, where 8
        # heads pad to 128 lanes: 26 MB a buffer, K and V, there and back
        # (paged_flash_decode_quant's docstring; the decode burst pays the
        # same). The parent's int8 programs held 329 MB.
        budget = 6 * pages * CHIP_PAGE_SIZE * 128 * 4
    assert compiled.memory_analysis().temp_size_in_bytes < budget


# --- the latent mixture's decode burst, as the chip's compiler leaves it -----

def test_compiled_latent_moe_burst_copies_no_pool_and_no_experts(one_chip,
                                                                 monkeypatch):
    """Two decode steps of a latent-attention mixture (kanana-2-30b-a3b's
    widths, one dense and one expert layer) under a scan, compiled for a
    v5e: per step and layer one latent attention kernel, per expert layer
    one expert kernel (64 rows: in place); the compiler materializes no
    layer of either
    pool and no layer's experts, and copies no pool whole. (With a rope pool
    64 lanes wide it copied all of it, 201 MB, into a tiled layout at every
    kernel call; with `w[layer]` handed to the grouped product it copied
    three `bf16[128,2048,768]` a layer, more than half a decode step:
    PERF.md section 6, PR 31.)"""
    hlo = _compiled_burst(
        one_chip, monkeypatch, deepseek_v3, LATENT_CFG, pages=LATENT_PAGES,
        rows=LATENT_ROWS, window=2048,
        kernels=(pallas_attention.paged_latent_decode,
                 pallas_moe.grouped_expert_matmul,
                 pallas_moe.expert_rows_in_place)).as_text()
    # two attention kernels (a layer each) and the expert layer's one kernel
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2 + 1
    results = re.findall(r"^\s*(?:ROOT )?%\S+ = (\S+) ([\w\-]+)\(", hlo, re.M)
    assert results
    a_layer = r"bf16\[768,128,(512|128)\]"  # of either pool
    a_layers_experts = r"bf16\[128,(2048,768|768,2048)\]"
    whole_pool = r"bf16\[2,768,128,(512|128)\]"
    bad = [(shape, op) for shape, op in results
           if re.match(a_layer, shape) or re.match(a_layers_experts, shape)
           or (op == "copy" and re.match(whole_pool, shape))]
    assert not bad, bad


# --- a block pass, as the chip's compiler leaves it ---------------------------

@pytest.mark.parametrize("blocks", [1, 2], ids=["one-block", "two-blocks"])
def test_compiled_block_pass_runs_its_kernels_and_copies_no_pool(
        one_chip, monkeypatch, blocks):
    """The block pass of a family that generates by diffusion over blocks
    (models/sdar_moe.verify_step_paged: 32 rows of a block of 4 behind their
    committed caches, logits at every position) at the benchmark cell's pool
    and widths, compiled for a v5e: Mosaic takes the extend kernel under the
    block mask at 4 queries a row and the grouped expert matmul at 128 rows,
    no value pool is copied or sliced by the layer, and the temporaries are
    the pass's logits, not a layer of the pool or of the experts. And the
    scheduler's pass, two blocks wide with one block's logits a row from
    the row's own offset: 8 queries a row, 256 rows, the same kernels and
    the logits of 4 positions a row, no more."""
    from llmlb_tpu.ops import pallas_moe

    monkeypatch.setenv("LLMLB_TPU_ATTENTION", "pallas")
    monkeypatch.setattr(pallas_attention, "_interpret_default", lambda: False)
    monkeypatch.setattr(pallas_moe, "_interpret_default", lambda: False)
    jitted = (sdar_moe.verify_step_paged, pallas_attention.paged_flash_extend,
              pallas_moe.grouped_expert_matmul,
              pallas_moe.expert_rows_in_place)
    for f in jitted:
        f._clear_cache()
    on_chip = functools.partial(_on_chip, one_chip)
    cfg, b = BLOCK_CFG, BLOCK_CFG.block_length
    params = on_chip(jax.eval_shape(
        lambda key: sdar_moe.init_params(cfg, key), jax.random.PRNGKey(0)))
    pools = on_chip(jax.eval_shape(
        lambda: sdar_moe.init_kv_pages(cfg, BLOCK_PAGES, CHIP_PAGE_SIZE)))
    ids = on_chip(jax.ShapeDtypeStruct((CHIP_ROWS, blocks * b), jnp.int32))
    rows = on_chip(jax.ShapeDtypeStruct((CHIP_ROWS,), jnp.int32))
    tables = on_chip(jax.ShapeDtypeStruct((CHIP_ROWS, CHIP_TABLE), jnp.int32))
    wide = dict(logits_from=rows, logits_len=b) if blocks > 1 else {}
    try:
        with jax.default_matmul_precision("default"):
            compiled = sdar_moe.verify_step_paged.lower(
                params, cfg, ids, rows, rows, tables, *pools, None,
                window=1024, **wide).compile()
    finally:
        for f in jitted:
            f._clear_cache()
    hlo = compiled.as_text()
    # under the layer scan: one extend kernel and three grouped products
    assert hlo.count('custom_call_target="tpu_custom_call"') == 4
    results = re.findall(r"^\s*(?:ROOT )?%\S+ = (\S+) ([\w\-]+)\(", hlo, re.M)
    assert results
    # the pool as it is stored or as a page's [PS*K, D] rows, the view the
    # extend kernel takes at a block pass's q block
    pool = r"bf16\[(2,|1,)?544,(128,4|512),128\]"
    experts = r"bf16\[(2,|1,)?128,(2048,768|768,2048)\]"
    moved = ("copy", "copy-start", "copy-done", "dynamic-slice",
             "dynamic-update-slice", "concatenate", "transpose")
    bad = [(shape, op) for shape, op in results if op in moved
           and (re.match(pool, shape) or re.match(experts, shape))]
    assert not bad, bad
    # the extend kernel takes the page as it is stored at 4 and at 8 queries:
    # its call's result is the chunk as it lies, [rows, T*H, D]
    assert pallas_attention.extend_body(blocks * b, cfg.num_heads,
                                        cfg.num_kv_heads,
                                        CHIP_PAGE_SIZE) == "page"
    assert re.search(
        rf"= bf16\[{CHIP_ROWS},{blocks * b * cfg.num_heads},128\]\S* "
        r"custom-call\(", hlo), "the extend call's result"
    logits = CHIP_ROWS * b * cfg.vocab_size * 4
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * logits


# --- a hybrid's decode burst: a state per slot beside the pages ---------------

def test_compiled_hybrid_burst_updates_the_state_in_place_and_copies_no_experts(
        one_chip, monkeypatch):
    """Two decode steps of a hybrid of state-space, attention and expert
    layers (nemotron-3-nano-30b-a3b's widths, two layers of each kind, 64 of
    128 experts held, the benchmark cell's 544 pages and 32 slots) under a
    scan, compiled for a v5e: per step one state kernel a state-space layer,
    one attention kernel an attention layer and two grouped products an
    expert layer; the compiler materializes no layer of the recurrent state
    (67 MB at 32 slots), of the page pool or of the experts, and copies none
    of the three whole. (With the up-projection stored `[K, 1856]` the chip
    laid it out K-minor and copied the whole stack, 3.8 GB, into the kernel's
    layout on every call: PERF.md section 6, PR 38.)"""
    cfg = nemotron_h.NemotronHConfig(
        vocab_size=131072, hidden_size=2688, intermediate_size=1856,
        num_layers=6, num_heads=32, num_kv_heads=2, head_dim=128,
        rms_eps=1e-5, max_position_embeddings=4096, pattern="M*EM*E",
        num_experts=64, router_experts=128, tie_word_embeddings=False)
    compiled = _compiled_burst(
        one_chip, monkeypatch, nemotron_h, cfg, pages=544, rows=CHIP_ROWS,
        window=2048, pool={"num_slots": CHIP_ROWS},
        kernels=(pallas_attention.paged_flash_decode,
                 pallas_moe.grouped_expert_matmul,
                 pallas_moe.expert_rows_in_place, ssm.ssm_decode_step))
    hlo = compiled.as_text()
    # two layers of each kind: a state kernel, an attention kernel, a mixture
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2 * (1 + 1 + 1)
    results = re.findall(r"^\s*(?:ROOT )?%\S+ = (\S+) ([\w\-]+)\(", hlo, re.M)
    assert results
    a_layer = (r"f32\[32,64,64,128\]", r"bf16\[544,128,2,128\]",
               r"bf16\[64,(1856,2688|2688,1856)\]")
    whole = (r"f32\[2,32,64,64,128\]", r"bf16\[2,544,128,2,128\]",
             r"bf16\[2,64,(1856,2688|2688,1856)\]")
    moved = ("copy", "copy-start", "copy-done", "transpose")
    bad = [(shape, op) for shape, op in results
           if any(re.match(p, shape) for p in a_layer)
           or (op in moved and any(re.match(p, shape) for p in whole))]
    assert not bad, bad
    assert not _coefficient_rows(hlo, results)
    # the temporaries are the step's logits and activations, not the state
    state = 2 * CHIP_ROWS * 64 * 64 * 128 * 4
    assert compiled.memory_analysis().temp_size_in_bytes < state / 2


# --- a shortcut-connected mixture's decode burst -------------------------------

SHORTCUT_CFG = longcat_flash.LongcatFlashConfig(  # longcat-flash-omni-l4's
    # widths, two double layers deep: 16 of 512 experts held behind a router
    # of 768 outputs, queries through a latent of 1,536
    vocab_size=16384, hidden_size=6144, intermediate_size=12288,
    num_layers=2, num_heads=64, num_kv_heads=64, head_dim=64,
    rope_theta=1e7, rms_eps=1e-5, max_position_embeddings=131072,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, q_lora_rank=1536, q_lora_scale=2.0,
    kv_lora_scale=12 ** 0.5, num_experts=16, router_experts=512,
    zero_experts=256, experts_per_token=12, moe_intermediate_size=2048,
    routed_scaling_factor=6.0)


def test_compiled_shortcut_burst_runs_its_kernels_and_copies_no_experts(
        one_chip, monkeypatch):
    """Two decode steps of double layers with a shortcut-connected mixture
    (longcat-flash-omni-l4's widths and its four layers = eight attention
    sub-layers, the benchmark cell's 544 pages and 32 rows) under a scan,
    compiled for a v5e: per step one latent attention kernel a SUB-layer at
    64 heads and one expert kernel a mixture; the compiler materializes
    no layer of either pool and no layer's experts, and copies no pool
    whole. At the cell's depth, because what the compiler stages through
    its fast memory (`S(1)`) follows what fits there: since no slice of
    `wq_b` is laid out again in it (PR 47) a rope pool of two layers, 71 MB,
    is copied in and out around its first write every step; the cell's, 143
    MB, stays where it is."""
    cfg = dataclasses.replace(SHORTCUT_CFG, num_layers=4)
    hlo = _compiled_burst(
        one_chip, monkeypatch, longcat_flash, cfg, pages=544, rows=CHIP_ROWS,
        window=1024, kernels=(pallas_attention.paged_latent_decode,
                              pallas_moe.grouped_expert_matmul,
                              pallas_moe.expert_rows_in_place)).as_text()
    # eight attention sub-layers' kernels and four mixtures' one each
    assert hlo.count('custom_call_target="tpu_custom_call"') == 8 + 4
    results = re.findall(r"^\s*(?:ROOT )?%\S+ = (\S+) ([\w\-]+)\(", hlo, re.M)
    assert results
    a_layer = r"bf16\[544,128,(512|128)\]"  # of either pool
    a_layers_experts = r"bf16\[16,(6144,2048|2048,6144)\]"
    whole = (r"bf16\[8,544,128,(512|128)\]",
             r"bf16\[4,16,(6144,2048|2048,6144)\]")
    moved = ("copy", "copy-start", "copy-done", "transpose")
    bad = [(shape, op) for shape, op in results
           if re.match(a_layer, shape) or re.match(a_layers_experts, shape)
           or (op in moved and any(re.match(p, shape) for p in whole))]
    assert not bad, bad
    # the branch is named in the trace, and so is each sub-layer
    for scope in ("sublayer0", "sublayer1", "deferred_branch"):
        assert scope in hlo, scope


# --- a projection that splits into heads, as the chip's compiler leaves it ----

WINDOW_CFG = mimo_v2.MimoV2Config(  # mimo-v2-5-l7's widths: a global layer over
    # a dense feed-forward, a window layer over a mixture of 16 held experts
    vocab_size=19072, hidden_size=4096, intermediate_size=16384,
    num_layers=2, num_heads=64, num_kv_heads=4, head_dim=192, rope_theta=1e7,
    rms_eps=1e-5, max_position_embeddings=1048576,
    partial_rotary_factor=0.334, value_scale=0.707, num_experts=16,
    tie_word_embeddings=False)
HEAD_SPLIT = {  # `_compiled_burst`'s keywords, a configuration's widths a case
    "mistral-wq-wk-wv": dict(
        family=llama, cfg=CHIP_CFG, pages=CHIP_PAGES, rows=CHIP_ROWS,
        window=512, kernels=(pallas_attention.paged_flash_decode,)),
    "kanana-wq": dict(
        family=deepseek_v3, cfg=LATENT_CFG, pages=LATENT_PAGES,
        rows=LATENT_ROWS, window=2048,
        kernels=(pallas_attention.paged_latent_decode,
                 pallas_moe.grouped_expert_matmul,
                 pallas_moe.expert_rows_in_place)),
    "longcat-wq_b": dict(
        family=longcat_flash, cfg=SHORTCUT_CFG, pages=544, rows=CHIP_ROWS,
        window=1024, kernels=(pallas_attention.paged_latent_decode,
                              pallas_moe.grouped_expert_matmul,
                              pallas_moe.expert_rows_in_place)),
    "mimo-wq-wk-wv": dict(
        family=mimo_v2, cfg=WINDOW_CFG, pages=544, rows=CHIP_ROWS,
        window=2048, pool={"num_slots": CHIP_ROWS},
        kernels=(pallas_attention.paged_flat_decode,
                 pallas_attention.paged_flash_decode,  # a ring a page
                 pallas_moe.grouped_expert_matmul,
                 pallas_moe.expert_rows_in_place)),
}
HEAD_SPLIT_WEIGHT = re.compile(r"(?:^|_)(wq|wk|wv|wq_b)$")


def _relaid_head_split_weights(hlo, cfg, params):
    """Every result of the compiled program `hlo` that is a layer (or more)
    of a head-split weight of `params` laid out again: shaped `[l, E, H*D]`
    like a piece of the stack but with another minor dimension than the
    stack's own, or shaped `[H, D, E]`, the operand of the convolution the
    split folds the product into."""
    stacks = {name: w.shape for name, w in params.items()
              if HEAD_SPLIT_WEIGHT.search(name) and len(w.shape) == 3}
    assert stacks
    heads = {cfg.num_heads, cfg.num_kv_heads,
             getattr(cfg, "window_kv_heads", cfg.num_kv_heads)}
    found = []
    for shape, layout, op in re.findall(
            r"^\s*(?:ROOT )?%\S+ = \w+\[([\d,]+)\]\{([\d,]*)\S* ([\w\-]+)\(",
            hlo, re.M):
        dims = [int(d) for d in shape.split(",")]
        for name, (layers, e, n) in stacks.items():
            if ((len(dims) == 3 and dims[0] <= layers and dims[1:] == [e, n]
                 and layout != "2,1,0")
                    or (len(dims) in (3, 4) and dims[-1] == e
                        and dims[-3] in heads and dims[-3] * dims[-2] == n
                        and op != "bitcast")):
                found.append((name, shape, layout, op))
                break
    return found


@pytest.mark.parametrize("case", sorted(HEAD_SPLIT))
def test_compiled_decode_burst_transposes_no_head_split_weight(
        case, one_chip, monkeypatch):
    """Two decode steps under a scan, compiled for a v5e at the widths of
    the benchmark's configurations, two layers deep: no layer's slice of
    `wq`, `wk`, `wv` (Mistral-7B; MiMo-V2.5 at heads of 192, both kinds of
    layer) or of a latent attention's `wq` (kanana-2-30b-a3b) or `wq_b`
    behind a query latent (longcat-flash-omni, 64 heads) is transposed in
    front of its product. Before `llama._proj_heads` the compiler folded the
    split into heads into the product and every step held a
    `bf16[1,4096,4096]{1,2,0}` fusion a layer, the stack's minor dimension
    moved to the contracted one, and `bf16[8,128,4096]` for `wk` and `wv`:
    2.0 ms of Mistral-7B's 13.2 ms step (PERF.md section 6, PR 47)."""
    burst = HEAD_SPLIT[case]
    hlo = _compiled_burst(one_chip, monkeypatch, **burst).as_text()
    params = jax.eval_shape(
        lambda key: burst["family"].init_params(burst["cfg"], key),
        jax.random.PRNGKey(0))
    assert not _relaid_head_split_weights(hlo, burst["cfg"], params)


def test_a_head_split_projection_equals_the_plain_one_bit_for_bit():
    """`llama._proj_heads` is `_proj` behind a barrier: the identity on
    values, bf16 and float32, with and without an int8 weight's scale."""
    key = jax.random.PRNGKey(7)
    for dtype in (jnp.float32, jnp.bfloat16):
        x = jax.random.normal(key, (2, 3, 64), dtype)
        w = jax.random.normal(jax.random.fold_in(key, 1), (64, 4 * 16), dtype)
        for lp in ({"wq": w},
                   {"wq": (w * 8).astype(jnp.int8),
                    "wq_scale": jnp.full((4 * 16,), 0.125, jnp.float32)}):
            plain = jax.jit(lambda lp, x: llama._proj(lp, "wq", x))(lp, x)
            split = jax.jit(lambda lp, x: llama._proj_heads(lp, "wq", x)
                            .reshape(2, 3, 4, 16))(lp, x)
            assert split.dtype == plain.dtype
            assert (split.reshape(plain.shape) == plain).all()


def test_compiled_sampler_sorts_no_vocabulary(one_chip):
    """`ops/sampling.sample_tokens` at the block pass's `f32[128, 151936]`,
    compiled for a v5e: no `TopK` custom call and no `sort` sees a row of
    more than the 64 picked groups' members (until PR 35 one `TopK` over
    the vocabulary was a fifth of the pass, PERF.md §6), and the
    temporaries stay under two copies of the logits. The guard against a
    later edit that puts the vocabulary-wide sort back."""
    from llmlb_tpu.ops import sampling

    rows, vocab = 128, BLOCK_CFG.vocab_size
    plan = sampling.selection_plan(vocab)
    assert plan["group"] and plan["sorted_per_row"] < vocab // 8
    on_chip = functools.partial(_on_chip, one_chip)
    per_row = on_chip(jax.ShapeDtypeStruct((rows,), jnp.float32))
    ints = on_chip(jax.ShapeDtypeStruct((rows,), jnp.int32))
    logits = on_chip(jax.ShapeDtypeStruct((rows, vocab), jnp.float32))
    key = on_chip(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    compiled = jax.jit(sampling.sample_tokens).lower(  # as _sample_block does
        logits, key, per_row, per_row, ints, None, ints, ints).compile()
    hlo = compiled.as_text()
    shape_of = dict(re.findall(
        r"^\s*(?:ROOT )?(%\S+) = \(?(\w+\[[\d,]*\])", hlo, re.M))
    sorted_over = []  # the minor dimension of every sort's and TopK's operand
    for line in hlo.splitlines():
        m = re.search(r" (sort|custom-call)\((%[^,)\s]+)", line)
        if not m or (m.group(1) == "custom-call"
                     and 'custom_call_target="TopK"' not in line):
            continue
        dims = re.search(r"\[([\d,]*)\]", shape_of[m.group(2)]).group(1)
        sorted_over.append(int(dims.split(",")[-1]))
    # the group maxima, the 64 picked groups put in order, the candidates
    k = sampling.TOPK_PREFILTER
    assert len(sorted_over) >= 3, sorted_over
    assert max(sorted_over) <= k * plan["group"], sorted_over
    assert sum(sorted_over) <= plan["sorted_per_row"] + k, sorted_over
    assert (compiled.memory_analysis().temp_size_in_bytes
            < 2 * rows * vocab * 4)
