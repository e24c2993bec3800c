"""The comparison behind `correct`, part (a): the engine's own serving
functions — prefill through the block table into a page pool, chunked
extends, then single-token decode steps through the paged cache, with the
kernels the engine dispatches to — against the plain float32 reference's
one whole-sequence forward pass, at the logits, on the configuration's real
widths and the run's seeded weights. Teacher-forced: both sides see the same
seeded token ids, because with random weights the largest logit flips on
rounding.

For a mixture the comparison has two parts, because a router's near tie
decided the other way by bf16 rounding is a different function and not an
error (benchmark/reference/moe.py). The reference mixes the experts the
program chose, so the logits compare like a dense model's; and the
program's choices are judged on their own: its router logits against the
reference's, each choice a top-k of the program's own logits, every choice
that differs from the reference's own top-k a near tie by the reference's
account, and no assignment dropped. A reference says that it wants this by
declaring `FOLLOWS = "routing"`; one that declares nothing is compared as
it always was.

Runs in the launcher, before the engine's page pool is allocated, on a small
pool of its own (the reference's float32 expert weights need the room).
"""

from __future__ import annotations

import random

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import routing
from benchmark.reference import module_for


def rel_rms_err(got, want) -> float:
    """||got − want|| / ||want|| over all logits: the error as a share of the
    logits' own scale."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / max(np.sqrt(np.mean(want ** 2)), 1e-30))


def routing_verdict(chosen, logits, kept, want_logits, spec: dict) -> dict:
    """The program's routing `chosen` [L, T, k], `logits` [L, T, X] and
    `kept` [L, T, k] against the reference's own router logits
    `want_logits` [L, T, X] (computed on the reference's float32 hidden
    state, with the program's choices followed in the layers before)."""
    chosen, kept = np.asarray(chosen), np.asarray(kept, bool)
    logits = np.asarray(logits, np.float64)
    want = np.asarray(want_logits, np.float64)
    k, experts = chosen.shape[-1], want.shape[-1]
    ranked = np.sort(logits, axis=-1)
    own_topk = bool(
        (np.take_along_axis(logits, chosen, -1) >= ranked[..., -k, None]).all()
        and (np.diff(np.sort(chosen, -1), axis=-1) != 0).all())
    want_chosen = np.argsort(-want, axis=-1, kind="stable")[..., :k]
    agree = (np.sort(chosen, -1) == np.sort(want_chosen, -1)).all(-1)  # [L, T]
    # a flip is rounding's where the reference's own scores of the last
    # expert in and the first one out lie within the router logits' error
    noise = np.sqrt(np.mean((logits - want) ** 2, axis=(1, 2)))  # [L]
    margin = np.zeros(agree.shape)
    if experts > k:
        want_ranked = np.sort(want, axis=-1)
        margin = ((want_ranked[..., -k] - want_ranked[..., -k - 1])
                  / np.maximum(noise, 1e-30)[:, None])
    flip_margins = margin[~agree]
    return {
        "router_rel_rms_err": max(rel_rms_err(g, w)
                                  for g, w in zip(logits, want)),
        "router_tolerance": float(spec["router_tolerance"]),
        "choice_is_own_topk": own_topk,
        "routing_agreement": float(agree.mean()),
        "flips": int((~agree).sum()),
        "flips_at_wide_margin": int(
            (flip_margins > float(spec["flip_margin_multiple"])).sum()),
        "flip_margin_multiple": float(spec["flip_margin_multiple"]),
        "widest_flip_margin": float(flip_margins.max(initial=0.0)),
        "dropped_assignments": int((~kept).sum()),
    }


def check(family, cfg, params, hf: dict, spec: dict, seed: int,
          page_size: int, reference=None) -> dict:
    """spec: prefill_tokens, extend_chunks, extend_tokens, decode_steps,
    tolerance, and for a reference that follows routing router_tolerance
    and flip_margin_multiple (see the configuration file for the reasons
    behind them). `reference`: the configuration's module, by default the
    one `benchmark/reference/` has for it."""
    reference = reference or module_for(hf)
    follows = getattr(reference, "FOLLOWS", None)
    if follows not in (None, "routing"):
        raise ValueError(f"{reference.__name__} follows {follows!r}, which "
                         "this comparison cannot give it")
    p0 = int(spec["prefill_tokens"])
    chunks, ct = int(spec.get("extend_chunks", 0)), int(spec.get("extend_tokens", 32))
    steps = int(spec["decode_steps"])
    total = p0 + chunks * ct + steps
    rng = random.Random(seed ^ 0x5EED)
    ids = np.asarray([rng.randrange(8, cfg.vocab_size) for _ in range(total)],
                     np.int32)

    window = 256
    while window < total + 1:
        window *= 2
    ppn = -(-window // page_size)
    cache_k, cache_v = family.init_kv_pages(cfg, ppn + 1, page_size)
    table = jnp.asarray(np.arange(1, ppn + 1, dtype=np.int32)[None, :])

    def serving(name):
        return (routing.observed(family, name) if follows
                else getattr(family, name))

    prefill, extend, decode = map(serving, (
        "prefill_into_pages", "prefill_extend_pages", "decode_step_paged"))
    rows = []  # (position whose next-token logits these are, logits [V])
    routes = []  # each call's (chosen, router logits, kept), [L, 1, T, .]
    logits, cache_k, cache_v, *route = prefill(
        params, cfg, jnp.asarray(ids[None, :p0]), jnp.asarray([p0], np.int32),
        table, cache_k, cache_v, None)
    rows.append((p0 - 1, logits[0]))
    routes += route
    pos = p0
    for _ in range(chunks):
        logits, cache_k, cache_v, *route = extend(
            params, cfg, jnp.asarray(ids[None, pos:pos + ct]),
            jnp.asarray([ct], np.int32), jnp.asarray([pos], np.int32),
            table, cache_k, cache_v, None)
        pos += ct
        rows.append((pos - 1, logits[0]))
        routes += route
    for _ in range(steps):
        logits, cache_k, cache_v, *route = decode(
            params, cfg, jnp.asarray(ids[pos:pos + 1]),
            jnp.asarray([pos], np.int32), cache_k, cache_v, table, None,
            window=window)
        rows.append((pos, logits[0]))
        routes += route
        pos += 1
    got = np.stack([np.asarray(r, np.float32) for _, r in rows])
    del cache_k, cache_v

    if follows:
        # every token went through exactly one call: stitched by position
        chosen, router_logits, kept = (
            np.concatenate([np.asarray(r[i])[:, 0] for r in routes], axis=1)
            for i in range(3))
        want_all, want_router = reference.forward(params, hf, ids,
                                                  follow=chosen)
        routed = routing_verdict(chosen, router_logits, kept, want_router,
                                 spec)
    else:
        want_all = reference.forward(params, hf, ids)
    want = np.asarray(want_all, np.float32)[[p for p, _ in rows]]
    errs = [rel_rms_err(g, w) for g, w in zip(got, want)]
    tol = float(spec["tolerance"])
    out = {
        "ok": bool(max(errs) <= tol and np.isfinite(got).all()),
        "tolerance": tol,
        "max_rel_rms_err": max(errs),
        "prefill_rel_rms_err": errs[0],
        "decode_rel_rms_err": max(errs[-steps:]) if steps else None,
        "positions_compared": len(rows),
        "tokens": total,
    }
    if follows:
        # each ground of a refusal by its name; `ok` needs none
        grounds = [name for name, sound in (
            ("logits", out["ok"]),
            ("router_rel_rms_err",
             routed["router_rel_rms_err"] <= routed["router_tolerance"]),
            ("choice_is_own_topk", routed["choice_is_own_topk"]),
            ("flips_at_wide_margin", routed["flips_at_wide_margin"] == 0),
            ("dropped_assignments", routed["dropped_assignments"] == 0),
        ) if not sound]
        out.update(routed, ok=not grounds, grounds=grounds)
    return out
