"""The plain float32 references against models/llama.py and
models/mixtral.py at a tiny width: prefill through the block table, a
chunked extend, then decode steps through the paged cache, compared at the
logits — the comparison the launcher makes at full width on the chip
(benchmark/correctness.py). For the mixture also what that comparison says
of the routing: a near tie decided the other way passes, and a wrong expert,
a wrong router, a skipped renormalisation, a choice that is no top-k, a flip
at a wide margin and a dropped assignment each fail by the field that names
them.

What these tests may require of the program is the contract the harness
documents and no more: the family's three paged serving functions with
their signatures and three results, `init_params`, `init_kv_pages`, that
`ops.moe.top_k_routing(router_logits [S, X], k)` is called once per layer
of a traced call (the tap's way in), and, where a family has it, the static
`routing=True` argument (benchmark/routing.py). Not that the program drops
assignments, has a capacity dispatch, or lacks that argument: each case
below holds on a program that drops nothing, has no capacity code and
offers its routing, and on today's."""

import inspect
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correctness, manifest as mf, reference as refs, routing
from benchmark.launcher import build_cfg
from benchmark.reference import dense, moe
from llmlb_tpu.models import family_for, mixtral
from llmlb_tpu.ops import moe as moe_ops

HERE = os.path.dirname(os.path.abspath(__file__))
REHEARSAL = os.path.join(HERE, "rehearsal")
ROUTER_SPEC = {"router_tolerance": 1e-4, "flip_margin_multiple": 6.0}


def load(name, **changes):
    with open(os.path.join(REHEARSAL, "configs", name + ".json")) as f:
        config = json.load(f)
    config.update(changes)
    return config


def keeping(module, true_params, follow=True):
    """`module` as a reference that keeps the true weights whatever the
    engine was given; with `follow=False` it is not told the routing: the
    comparison of before PR 26."""
    def forward(_params, hf, ids, **kw):
        if not follow:
            kw.pop("follow", None)
        return module.forward(true_params, hf, ids, **kw)

    return types.SimpleNamespace(
        forward=forward, FOLLOWS=getattr(module, "FOLLOWS", None),
        __name__=module.__name__)


def run(config, params=None, reference=None, family=None, **spec):
    """The comparison of `family` (by default the program's own for the
    configuration) with the configuration's reference."""
    cfg = build_cfg(config)
    program = family_for(cfg)
    if params is None:
        params = program.init_params(cfg, jax.random.PRNGKey(3))
    return correctness.check(
        family or program, cfg, params, config,
        {**config["correctness"], "tolerance": 1.0, **spec}, 7,
        config["engine"]["kv_page_size"],
        reference or refs.module_for(config, REHEARSAL)), params, program


@pytest.mark.parametrize("name", ["debug-tiny", "debug-moe-tiny",
                                  "debug-bias-tiny"])
def test_float32_engine_agrees_with_the_reference_to_rounding(name):
    out, _, _ = run(load(name))
    assert out["positions_compared"] == 1 + 1 + 4  # prefill, extend, 4 decodes
    assert out["max_rel_rms_err"] < 1e-4
    assert out["decode_rel_rms_err"] < 1e-4


DENSE_KEYS = {"ok", "tolerance", "max_rel_rms_err", "prefill_rel_rms_err",
              "decode_rel_rms_err", "positions_compared", "tokens"}


def test_a_reference_that_declares_nothing_is_compared_as_before():
    out, _, _ = run(load("debug-tiny"))
    assert set(out) == DENSE_KEYS and out["ok"] is True
    assert getattr(dense, "FOLLOWS", None) is None


def test_a_mixture_reports_its_routing_beside_the_logits():
    out, _, _ = run(load("debug-moe-tiny"))
    assert moe.FOLLOWS == "routing"
    assert set(out) == DENSE_KEYS | {
        "router_rel_rms_err", "router_tolerance", "choice_is_own_topk",
        "routing_agreement", "flips", "flips_at_wide_margin",
        "flip_margin_multiple", "widest_flip_margin", "dropped_assignments",
        "grounds"}
    assert out["ok"] is True and out["grounds"] == []
    assert out["router_rel_rms_err"] < 1e-5
    assert (out["choice_is_own_topk"], out["routing_agreement"], out["flips"],
            out["dropped_assignments"]) == (True, 1.0, 0, 0)


def test_a_near_tie_decided_the_other_way_passes_only_when_followed():
    """Layer 0's router scores experts 1 and 2 alike, exactly; the engine's
    copy of expert 2's column is larger by 2**-12, less than bf16 rounding
    (2**-8). Wherever the tie lies on the edge of the top 2 and is positive
    the engine takes expert 2 and float32 arithmetic expert 1 — another
    function. The old comparison refuses that engine; the one that follows
    its routing finds the logits within rounding, every flip at a margin of
    0 and nothing else wrong."""
    config = load("debug-moe-tiny")
    cfg = build_cfg(config)
    true = mixtral.init_params(cfg, jax.random.PRNGKey(3))
    router = true["router"]
    true["router"] = router.at[0, :, 2].set(router[0, :, 1])
    engine = {**true, "router": true["router"].at[0, :, 2].multiply(
        1 + 2.0 ** -12)}
    spec = {"tolerance": 0.005, "router_tolerance": 0.005}
    old, _, _ = run(config, params=engine,
                    reference=keeping(moe, true, follow=False), **spec)
    new, _, _ = run(config, params=engine, reference=keeping(moe, true), **spec)
    assert new["flips"] >= 1 and new["flips_at_wide_margin"] == 0
    assert new["widest_flip_margin"] < 0.5 and new["choice_is_own_topk"]
    assert new["ok"] and new["max_rel_rms_err"] < 0.002
    assert not old["ok"] and "logits" in old["grounds"]
    assert old["max_rel_rms_err"] > 100 * new["max_rel_rms_err"]


def _zeroed_expert(params):
    return {**params, "we_down": params["we_down"].at[0, 1].set(0)}


def _permuted_router(params):
    return {**params, "router": params["router"].at[1].set(
        jnp.roll(params["router"][1], 1, axis=-1))}


def _no_renormalisation(router_logits, num_selected):
    """A softmax over ALL experts, the k largest kept as they are: the sum
    of a token's weights is under 1."""
    vals, idx = jax.lax.top_k(jax.nn.softmax(router_logits, axis=-1),
                              num_selected)
    return vals, idx


def _first_and_third(router_logits, num_selected):
    vals, idx = jax.lax.top_k(router_logits, num_selected + 1)
    keep = jnp.asarray([0, 2])
    return jax.nn.softmax(vals[:, keep], axis=-1), idx[:, keep]


@pytest.fixture
def patch_top_k():
    """`patch_top_k(wrong)` puts `wrong` in `ops.moe.top_k_routing`'s place
    for the rest of the test. Whoever traces the program's body under the
    patch — the tap's own jit, or a family that is asked and jits for
    itself — traces it anew (nothing made before the patch is found) and
    keeps nothing of it (nothing made under the patch is found after)."""
    def forget():
        routing.observed.cache_clear()
        jax.clear_caches()

    patched = []
    with pytest.MonkeyPatch.context() as patch:
        def put(wrong):
            forget()
            patch.setattr(moe_ops, "top_k_routing", wrong)
            patched.append(wrong)

        yield put
    if patched:
        forget()


@pytest.mark.parametrize("wrong_params,wrong_top_k,grounds", [
    (_zeroed_expert, None, {"logits"}),
    (_permuted_router, None, {"router_rel_rms_err"}),
    (None, _no_renormalisation, {"logits"}),
    (None, _first_and_third, {"choice_is_own_topk", "flips_at_wide_margin"}),
], ids=["wrong_expert_weight", "wrong_router", "skipped_renormalisation",
        "choice_that_is_no_top_k"])
def test_a_wrong_mixture_fails_by_the_field_that_names_it(
        patch_top_k, wrong_params, wrong_top_k, grounds):
    config = load("debug-moe-tiny")
    cfg = build_cfg(config)
    true = mixtral.init_params(cfg, jax.random.PRNGKey(3))
    if wrong_top_k:
        patch_top_k(wrong_top_k)
    out, _, _ = run(config, reference=keeping(moe, true), tolerance=0.01,
                    params=wrong_params(true) if wrong_params else true)
    assert not out["ok"] and grounds <= set(out["grounds"]), out
    if wrong_top_k is _no_renormalisation:
        # every choice is sound; the mixing weights are wrong (and with them
        # what layer 1's router is given)
        assert out["choice_is_own_topk"] and out["max_rel_rms_err"] > 0.05


def reporting(not_kept=None):
    """A family that offers its routing (the `routing=` argument of
    benchmark/routing.py) and answers with what the program's own routing
    is observed to be, but for `kept`: the pairs `not_kept[name]` lists, as
    indices into [L, B, T, k], are reported as not kept. `reported` counts
    the pairs reported so, the program's own among them."""
    reported = []
    observed = routing.observed  # `routing` is the argument's name below

    def serving(name):
        def fn(*args, routing=False, **kw):
            assert routing, "the comparison asks a family that offers it"
            *out, (chosen, logits, kept) = observed(mixtral, name)(*args, **kw)
            kept = np.array(kept, bool)
            for index in (not_kept or {}).get(name, ()):
                kept[index] = False
            reported.append(int((~kept).sum()))
            return (*out, (chosen, logits, kept))

        return staticmethod(fn)

    names = ("prefill_into_pages", "prefill_extend_pages", "decode_step_paged")
    family = type("reporting_family", (), {
        "init_kv_pages": staticmethod(mixtral.init_kv_pages),
        **{name: serving(name) for name in names}})
    return family, reported


def test_a_dropped_assignment_is_counted_and_named():
    """Shown on what the comparison is given, not on what the program does:
    a family whose reported routing says five pairs were not kept, three in
    the prefill and two in the chunk, at sizes where the program itself
    keeps every pair. Nothing else is wrong with it, and nothing else is
    found."""
    config = load("debug-moe-tiny")
    sound, reported = reporting()
    out, _, _ = run(config, family=sound)
    assert out["ok"] and out["dropped_assignments"] == sum(reported) == 0
    family, reported = reporting({
        "prefill_into_pages": [(0, 0, 3, 1), (1, 0, 3, 0), (1, 0, 15, 1)],
        "prefill_extend_pages": [(0, 0, 0, 0), (1, 0, 9, 1)]})
    out, _, _ = run(config, family=family)
    assert reported[:2] == [3, 2] and not any(reported[2:])
    assert out["dropped_assignments"] == 5
    assert out["grounds"] == ["dropped_assignments"] and not out["ok"]
    assert out["max_rel_rms_err"] < 1e-4  # the logits are the program's own


@pytest.mark.parametrize("prefill_tokens", [16, 32])
def test_what_the_program_reports_as_not_kept_is_what_is_counted(
        prefill_tokens):
    """A reading, and one rule: whatever `kept` the program's routing is
    observed to hold, `dropped_assignments` counts its false entries, and
    the ground is named exactly when there is one. 32 tokens are past the
    4 x experts up to which today's program keeps to its exact path; a
    program that drops nothing reads 0 at both sizes."""
    family, reported = reporting()
    out, _, _ = run(load("debug-moe-tiny"), family=family,
                    prefill_tokens=prefill_tokens, extend_chunks=0)
    print(f"prefill of {prefill_tokens}: {sum(reported)} of "
          f"{2 * 2 * (prefill_tokens + 4)} assignments reported as not kept")
    assert out["dropped_assignments"] == sum(reported)
    assert ("dropped_assignments" in out["grounds"]) == (sum(reported) > 0)
    assert out["ok"] == (not out["grounds"])


def _verdict(**changes):
    """Two layers, three tokens, four experts, top 2; the program agrees
    with the reference but for what `changes` says."""
    want = np.tile(np.asarray([4.0, 3.0, 1.0, 0.0]), (2, 3, 1))
    case = {"chosen": np.tile(np.asarray([0, 1]), (2, 3, 1)),
            "logits": want + 0.001 * np.asarray([1, -1, 1, -1]),
            "kept": np.ones((2, 3, 2), bool), "want_logits": want}
    case.update(changes)
    return correctness.routing_verdict(
        case["chosen"], case["logits"], case["kept"], case["want_logits"],
        ROUTER_SPEC)


def test_the_routing_verdict_by_hand():
    sound = _verdict()
    assert sound["router_rel_rms_err"] == pytest.approx(
        0.001 / np.sqrt(26 / 4))
    assert (sound["choice_is_own_topk"], sound["routing_agreement"],
            sound["flips"], sound["flips_at_wide_margin"],
            sound["dropped_assignments"]) == (True, 1.0, 0, 0, 0)
    # one decision of six flips where the reference's second and third
    # scores are 0.002 apart and the logits are off by 0.001 (RMS): margin 2
    want = np.tile(np.asarray([4.0, 3.0, 2.998, 0.0]), (2, 3, 1))
    logits = want + 0.001 * np.asarray([1, -1, 1, -1])
    chosen = np.tile(np.asarray([0, 1]), (2, 3, 1))
    chosen[1, 2] = [0, 2]
    logits[1, 2] = [4.0, 2.9985, 2.9995, 0.0]
    near = _verdict(want_logits=want, logits=logits, chosen=chosen)
    assert (near["flips"], near["flips_at_wide_margin"]) == (1, 0)
    assert near["routing_agreement"] == pytest.approx(5 / 6)
    assert near["widest_flip_margin"] == pytest.approx(2.0, rel=0.2)
    assert near["choice_is_own_topk"]


def test_a_flip_at_a_wide_margin_and_a_drop_are_counted():
    chosen = np.tile(np.asarray([0, 1]), (2, 3, 1))
    chosen[0, 1] = [0, 2]  # the reference scores expert 2 two whole points lower
    wide = _verdict(chosen=chosen)
    assert (wide["flips"], wide["flips_at_wide_margin"]) == (1, 1)
    assert not wide["choice_is_own_topk"]  # nor is it the program's own top 2
    kept = np.ones((2, 3, 2), bool)
    kept[1, 0, 1] = False
    assert _verdict(kept=kept)["dropped_assignments"] == 1
    twice = np.tile(np.asarray([0, 0]), (2, 3, 1))
    assert not _verdict(chosen=twice)["choice_is_own_topk"]


def test_the_capacity_rule_by_hand():
    """Three tokens, top 2, two places an expert: first choices of all
    tokens before second choices, tokens in order; padding takes no room
    and counts as kept; no capacity keeps all."""
    chosen = np.asarray([[0, 1], [0, 1], [0, 1], [1, 0]])
    valid = np.ones(4, bool)
    assert routing.kept_by_capacity(chosen, valid, 2, 3).tolist() == [
        [True, True], [True, False], [False, False], [True, False]]
    # token 1 is padding: it takes no place, so token 2 finds one
    padded = routing.kept_by_capacity(chosen, valid & (np.arange(4) != 1), 2, 3)
    assert padded.tolist() == [
        [True, True], [True, True], [True, False], [True, False]]
    assert routing.kept_by_capacity(chosen, valid, None, 3).all()


def test_the_tap_keeps_what_the_capacity_dispatch_keeps():
    """`routing.kept_by_capacity` is the harness's statement of the rule in
    `ops/moe.py`, and is held to the program's capacity dispatch for as
    long as the program has one: the dispatch must equal the exact mixture
    with exactly the pairs the statement says are dropped left out. A
    program with no such dispatch has nothing to hold it to, and the tap
    then keeps every pair of every call."""
    dispatch = getattr(moe_ops, "moe_dispatch_combine", None)
    s, m, f, e, k, cap = 24, 16, 32, 4, 2, 8
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(keys[0], (s, m), jnp.float32)
    logits = jax.random.normal(keys[1], (s, e), jnp.float32)
    wg, wu = (jax.random.normal(kk, (e, m, f), jnp.float32) * m ** -0.5
              for kk in keys[2:4])
    wd = jax.random.normal(keys[4], (e, f, m), jnp.float32) * f ** -0.5
    valid = np.arange(s) < 20
    weights, chosen = moe_ops.top_k_routing(logits, k)
    kept = routing.kept_by_capacity(np.asarray(chosen), valid, cap, e)
    assert 0 < (~kept).sum() < s * k
    if dispatch is not None:
        with jax.default_matmul_precision("highest"):
            got = dispatch(x, logits, wg, wu, wd, num_selected=k, capacity=cap,
                           token_valid=jnp.asarray(valid))
            want = jnp.zeros_like(x)
            for j in range(k):
                for ex in range(e):
                    w = jnp.where((chosen[:, j] == ex) & kept[:, j] & valid,
                                  weights[:, j], 0.0)
                    want = want + w[:, None] * dense.swiglu(
                        x, wg[ex], wu[ex], wd[ex])
        assert np.abs(np.asarray(got - want))[valid].max() < 1e-5
        return
    tapped = routing.observed(mixtral, "prefill_into_pages")
    if isinstance(tapped, routing._Tap):
        config = load("debug-moe-tiny")
        cfg = build_cfg(config)
        ck, cv = mixtral.init_kv_pages(cfg, 5, 16)
        *_, (_, _, kept) = tapped(
            mixtral.init_params(cfg, jax.random.PRNGKey(3)), cfg,
            jnp.zeros((1, 64), jnp.int32), jnp.asarray([64], jnp.int32),
            jnp.arange(1, 5, dtype=jnp.int32)[None, :], ck, cv, None)
        assert kept.shape == (2, 1, 64, 2) and kept.all()


def test_the_tap_is_off_the_serving_path():
    """The engine's programs are the ones they were: after a comparison that
    heard the routing, the program's own functions are the originals, their
    jaxprs hold no callback and no router-logit output, and the scheduler
    knows nothing of any of it."""
    def wrapped_while_tapped():
        return [moe_ops.top_k_routing] + [
            getattr(m, "moe_dispatch_combine", None) for m in (moe_ops, mixtral)]

    real = wrapped_while_tapped()
    config = load("debug-moe-tiny")
    # 32 tokens: past today's exact path, so every expert path the program
    # has was traced under the tap
    out, params, family = run(config, prefill_tokens=32, extend_chunks=0)
    assert out["positions_compared"] == 1 + 4
    assert all(now is was for now, was in zip(wrapped_while_tapped(), real))
    cfg = build_cfg(config)
    ck, cv = family.init_kv_pages(cfg, 5, 16)
    table = jnp.arange(1, 5, dtype=jnp.int32)[None, :]
    for jaxpr in (
        jax.make_jaxpr(lambda *a: family.decode_step_paged(
            *a, None, window=64), static_argnums=1)(
                params, cfg, jnp.zeros((1,), jnp.int32),
                jnp.asarray([3], jnp.int32), ck, cv, table),
        jax.make_jaxpr(lambda *a: family.prefill_into_pages(*a, None),
                       static_argnums=1)(
                params, cfg, jnp.zeros((1, 32), jnp.int32),
                jnp.asarray([32], jnp.int32), table, ck, cv),
    ):
        assert "callback" not in str(jaxpr)
        shapes = [v.aval.shape for v in jaxpr.jaxpr.outvars]
        assert len(shapes) == 3 and shapes[0] == (1, cfg.vocab_size)
    with open(os.path.join(mf.ROOT, "llmlb_tpu", "engine", "scheduler.py")) as f:
        scheduler = f.read()
    assert "routing=" not in scheduler and "benchmark" not in scheduler


def test_a_family_that_offers_its_routing_is_asked_and_not_tapped():
    class family:
        @staticmethod
        def prefill_into_pages(params, cfg, input_ids, *rest, routing=False):
            return ("logits", "k", "v", "its own routing") if routing else None

    asked = routing.observed(family, "prefill_into_pages")
    assert asked(None, None, None)[3] == "its own routing"
    # the program's own mixture is tapped exactly until it offers the same
    for name in ("prefill_into_pages", "prefill_extend_pages",
                 "decode_step_paged"):
        offers = "routing" in inspect.signature(
            getattr(mixtral, name)).parameters
        assert isinstance(routing.observed(mixtral, name),
                          routing._Tap) == (not offers)


def test_the_reference_follows_and_keeps_its_own_weights():
    """Told to mix other experts than its own top 2, the reference mixes
    them with weights from its OWN logits (a softmax over the chosen ones),
    and returns its own router logits beside the logits."""
    config = load("debug-moe-tiny")
    cfg = build_cfg(config)
    params = mixtral.init_params(cfg, jax.random.PRNGKey(3))
    ids = np.arange(8, 20, dtype=np.int32)
    logits, router = moe.forward(params, config, ids)
    assert router.shape == (2, 12, 4)
    own = np.asarray(jax.lax.top_k(router, 2)[1])
    same, _ = moe.forward(params, config, ids, follow=own)
    assert np.array_equal(np.asarray(same), np.asarray(logits))
    other = own.copy()
    other[1, 5] = [e for e in range(4) if e not in own[1, 5]]
    moved, router_moved = moe.forward(params, config, ids, follow=other)
    assert np.array_equal(np.asarray(router_moved[0]), np.asarray(router[0]))
    changed = np.abs(np.asarray(moved - logits)).max(axis=-1) > 1e-6
    assert changed[5] and not changed[:5].any()  # causal: from token 5 on


def test_a_configuration_names_its_reference_and_the_table_is_the_fallback():
    named = refs.module_for(load("debug-bias-tiny"), REHEARSAL)
    assert named.__file__.endswith("rehearsal/reference/qkv_bias.py")
    assert refs.module_for(load("debug-moe-tiny"), REHEARSAL).FOLLOWS == "routing"
    assert refs.module_for(load("debug-tiny")).__file__.endswith(
        "benchmark/reference/dense.py")
    with pytest.raises(mf.ManifestError, match="names no"):
        refs.module_for({"model_id": "x", "model_type": "unheard-of"})
    with pytest.raises(mf.ManifestError, match="has no file"):
        refs.module_for({"correctness": {"reference": "missing"}}, REHEARSAL)


def test_a_new_architectures_reference_computes_what_the_dense_one_does_not():
    """The rehearsal's architecture carries query, key and value biases
    (zero as initialised, so given values here): its own reference agrees
    with the program, the dense one does not."""
    config = load("debug-bias-tiny")
    cfg = build_cfg(config)
    assert cfg.attention_bias
    family = family_for(cfg)
    params = family.init_params(cfg, jax.random.PRNGKey(3))
    for i, name in enumerate(("bq", "bk", "bv")):
        params[name] = 0.5 * jax.random.normal(
            jax.random.PRNGKey(10 + i), params[name].shape, params[name].dtype)
    own, _, _ = run(config, params=params)
    plain, _, _ = run(config, params=params, reference=dense)
    assert own["max_rel_rms_err"] < 1e-4 < 0.01 < plain["max_rel_rms_err"]


def test_the_tolerance_separates_bf16_serving_from_int8_weights():
    """The chip's tolerance is about twice what bf16 activations cost; at
    that, int8 weights — a lower precision than the configuration states —
    must fail. Shown here at 8 layers of width 512."""
    from llmlb_tpu.quant import quantize_params

    config = load("debug-tiny", torch_dtype="bfloat16", hidden_size=512,
                  intermediate_size=1536, num_hidden_layers=8)
    bf16, params, _ = run(config)
    int8, _, _ = run(config, params=quantize_params(params),
                     reference=keeping(dense, params))
    tolerance = 2 * bf16["max_rel_rms_err"]
    assert bf16["max_rel_rms_err"] < 0.03
    assert int8["max_rel_rms_err"] > tolerance


@pytest.mark.parametrize("key,value", [("rope_theta", 500.0),
                                       ("rms_norm_eps", 0.5),
                                       ("num_hidden_layers", 1)])
def test_a_changed_or_skipped_term_fails(key, value):
    """The reference reads its own dimensions from the configuration: give
    it another rope base, norm epsilon or one layer fewer and the logits
    part by far more than any tolerance."""
    config = load("debug-tiny")
    cfg = build_cfg(config)
    family = family_for(cfg)
    params = family.init_params(cfg, jax.random.PRNGKey(3))
    wrong = {**config, key: value}
    out = correctness.check(family, cfg, params, wrong,
                            {**config["correctness"], "tolerance": 0.05}, 7, 16)
    assert not out["ok"] and out["max_rel_rms_err"] > 0.05


def test_rel_rms_err():
    want = np.array([[3.0, 4.0]])
    assert correctness.rel_rms_err(want, want) == 0.0
    assert correctness.rel_rms_err(want * 1.1, want) == pytest.approx(0.1)
