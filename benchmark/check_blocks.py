"""What `correct` cannot hold of a model that generates by diffusion over
blocks, held here on the chip at the published widths: BLOCK PASSES through
the family's own block-pass function (`verify_step_paged`, the call the
engine's burst program makes) with 0 to 3 of a block's positions masked,
ALL positions' logits and the routing against the plain reference's
whole-sequence pass, with controls that must fail. `correctness.check`
decodes one token a call and compares one position a call; its extend
chunks of one block ARE committing block passes, compared at the block's
last position. This adds the other positions and the passes that hold masks.
Every result is a JSON line on stdout and in
`chiprun_out/check_blocks/<model_id>.jsonl`.

    chiprun -- python3 benchmark/check_blocks.py --config <file> \
        --seeds 11,12,13 --cases program,causal_in_block,biased_choice,int8_weights

One seed and case: ROWS sequences of `--prefill` seeded tokens are prefilled
(whole blocks, to a page boundary), then for each of `--rounds` blocks every
row runs one pass with its own number of masked positions (row r masks r
mod B of them, at seeded places, `mask_token_id` in their stead) and then
the committing pass of the complete block — from a page boundary first, then
mid-page. The reference sees each row's committed ids and the block as the
pass saw it, follows the program's routing (benchmark/reference/moe.py says
why) and is compared at every position of the block by
`correctness.rel_rms_err`; the routing of every pass is judged by
`correctness.routing_verdict` with the configuration's limits. Cases:

  program          the program as it is
  causal_in_block  the program with the causal mask inside a block (its
                   attention built with a block length of 1): wrong at every
                   position, the last too through the layers below it
  no_qk_norm       the program without the RMS norm of query and key heads
  biased_choice    THE WRONG CHOICE: the program chooses its experts by its
                   router's logits plus a fixed per-expert offset of 0.1,
                   weighs them by the logits without it and reports those:
                   sound logits (the reference follows the choice), sound
                   scores, a choice wrong by far more than rounding. What
                   `flip_margin_multiple` is for.
  int8_weights     THE PRECISION CONTROL: every matrix rounded to int8 per
                   output channel and back in the program's place
                   (check_limits.rounded_to_int8), the reference on the
                   true weights, made again from the seed. Run it last.

A tool for the PR that adds such a configuration (PR 34); the driver does
not call it. For the next `benchmark` PR to merge into `correctness.check`
(PERF.md section 7).
"""

from __future__ import annotations

import argparse
import functools
import inspect
import json
import os
import random
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

ROWS = 4


def traced_apart(family, name: str, patch: list):
    """`family.<name>` traced under a jit of its own (another function than
    the program jits, so another trace cache) while each (module, attribute,
    value) of `patch` is in place."""
    import jax

    body = getattr(family, name).__wrapped__  # under the program's jax.jit
    names = inspect.signature(body).parameters

    @functools.wraps(body)
    def apart(*args, **kw):
        real = [(m, k, getattr(m, k)) for m, k, _ in patch]
        for m, k, v in patch:
            setattr(m, k, v)
        try:
            return body(*args, **kw)
        finally:
            for m, k, v in real:
                setattr(m, k, v)

    return jax.jit(
        apart,
        static_argnames=[n for n in ("cfg", "mesh", "window", "routing")
                         if n in names],
        donate_argnames=("cache_k", "cache_v"))


CHOICE_BIAS = 0.1  # of router logits of unit scale; rounding reads 0.008


def controls(family) -> dict:
    """case -> the (module, attribute, value) triples to put in place while
    the family's functions are traced for it."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from llmlb_tpu.models import llama
    from llmlb_tpu.ops import moe

    real_attention, real_top_k = family._attention, moe.top_k_routing

    def biased_choice(logits, k):
        bias = CHOICE_BIAS * jax.random.normal(
            jax.random.PRNGKey(7), logits.shape[-1:], jnp.float32)
        _, chosen = real_top_k(logits + bias, k)
        # weighed by the true logits, and the true logits reported
        picked = jnp.take_along_axis(logits, chosen, axis=-1)
        return jax.nn.softmax(picked, axis=-1), chosen

    return {
        "program": [],
        "int8_weights": [],
        "causal_in_block": [(family, "_attention", lambda cfg: real_attention(
            dataclasses.replace(cfg, block_length=1)))],
        "no_qk_norm": [(family, "_qk_norm_block", llama._attn_block)],
        "biased_choice": [(moe, "top_k_routing", biased_choice)],
    }


def one_check(serving, cfg, params, hf, spec, seed, page, reference,
              prefill_tokens: int, rounds: int, before_reference=None) -> dict:
    """One seed of one case: see the module's docstring. The program runs
    whole before the reference starts (`before_reference()` between them:
    the precision control swaps the weights there), so nothing is held
    twice. `reference.forward` is called with None for the parameters: the
    caller binds them."""
    import jax.numpy as jnp
    import numpy as np

    from benchmark import correctness

    b, mask_id = cfg.block_length, cfg.mask_token_id
    rng = random.Random(seed ^ 0xB10C)
    total = prefill_tokens + rounds * b
    ids = np.asarray([[rng.randrange(8, mask_id)  # never the mask itself
                       for _ in range(total)] for _ in range(ROWS)], np.int32)
    ppn = -(-(total + 1) // page)
    family = serving["family"]
    cache_k, cache_v = family.init_kv_pages(cfg, ROWS * ppn + 1, page)
    tables = jnp.asarray(
        1 + np.arange(ROWS * ppn, dtype=np.int32).reshape(ROWS, ppn))
    window = ppn * page

    logits, cache_k, cache_v, route = serving["prefill_into_pages"](
        params, cfg, jnp.asarray(ids[:, :prefill_tokens]),
        jnp.full((ROWS,), prefill_tokens, jnp.int32), tables, cache_k,
        cache_v, None, routing=True)
    committed = np.asarray(route[0])  # the choices [L, ROWS, T, k]
    passes = []  # the program's whole run first, then the reference's
    pos = prefill_tokens
    lens = jnp.full((ROWS,), b, jnp.int32)
    for _ in range(rounds):
        true = ids[:, pos:pos + b]
        seen = true.copy()
        for r in range(ROWS):
            for i in rng.sample(range(b), r % b):
                seen[r, i] = mask_id
        for block in (seen, true):  # a pass with masks, then the commit
            logits, cache_k, cache_v, route = serving["verify_step_paged"](
                params, cfg, jnp.asarray(block), lens,
                jnp.full((ROWS,), pos, jnp.int32), tables, cache_k, cache_v,
                None, window=window, routing=True)
            passes.append((pos, block, np.asarray(logits, np.float32),
                           *(np.asarray(x) for x in route)))
        committed = np.concatenate([committed, passes[-1][3]], axis=2)
        pos += b
    del cache_k, cache_v, logits
    params = None
    if before_reference is not None:
        before_reference()
    errs, worst, verdicts = [], {}, []
    for n, (pos, block, got, chosen, router_logits, kept) in enumerate(passes):
        for r in range(ROWS):
            if n % 2 and (block[r] == passes[n - 1][1][r]).all():
                continue  # the pass before this one saw the same block
            want, want_router = reference.forward(
                None, hf, np.concatenate([ids[r, :pos], block[r]]),
                follow=np.concatenate([committed[:, r, :pos], chosen[:, r]],
                                      axis=1))
            want = np.asarray(want, np.float32)[pos:]
            masks = int((block[r] == mask_id).sum())
            for i in range(b):
                e = correctness.rel_rms_err(got[r, i], want[i])
                errs.append(e)
                key = f"masked_{masks}_at_{i}"
                worst[key] = max(worst.get(key, 0.0), e)
            verdicts.append(correctness.routing_verdict(
                chosen[:, r], router_logits[:, r], kept[:, r],
                np.asarray(want_router)[:, pos:], spec))
    tol = float(spec["tolerance"])
    routed = {
        "router_rel_rms_err": max(v["router_rel_rms_err"] for v in verdicts),
        "choice_is_own_topk": all(v["choice_is_own_topk"] for v in verdicts),
        "flips": sum(v["flips"] for v in verdicts),
        "flips_at_wide_margin": sum(v["flips_at_wide_margin"]
                                    for v in verdicts),
        "widest_flip_margin": max(v["widest_flip_margin"] for v in verdicts),
        "dropped_assignments": sum(v["dropped_assignments"]
                                   for v in verdicts),
    }
    grounds = [name for name, sound in (
        ("logits", max(errs) <= tol and bool(np.isfinite(errs).all())),
        ("router_rel_rms_err",
         routed["router_rel_rms_err"] <= float(spec["router_tolerance"])),
        ("choice_is_own_topk", routed["choice_is_own_topk"]),
        ("flips_at_wide_margin", routed["flips_at_wide_margin"] == 0),
        ("dropped_assignments", routed["dropped_assignments"] == 0),
    ) if not sound]
    return {"ok": not grounds, "grounds": grounds, "tolerance": tol,
            "max_rel_rms_err": max(errs),
            "positions_compared": len(errs), "passes": len(verdicts),
            "worst_by_masks_and_position": worst, **routed}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--base", default=ROOT,
                    help="the directory of the manifest, for its references")
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--cases", default="program,causal_in_block,no_qk_norm,"
                    "biased_choice,int8_weights")
    ap.add_argument("--prefill", type=int, default=None,
                    help="tokens prefilled a row (default: one page)")
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--tag", default="", help="goes into every line")
    args = ap.parse_args()
    with open(args.config) as f:
        config = json.load(f)
    spec = config["correctness"]

    from llmlb_tpu.startup import configure_compile_cache, resolve_backend

    configure_compile_cache()
    devices = resolve_backend()
    from llmlb_tpu.models import family_for
    from llmlb_tpu.parallel.mesh import build_mesh

    from benchmark import check_limits, launcher, reference as refs

    cfg = launcher.build_cfg(config)
    family = family_for(cfg)
    reference = refs.module_for(config, args.base)
    mesh = build_mesh(launcher.mesh_config_for(cfg, len(devices)),
                      devices=devices)
    page = int(config["engine"].get("kv_page_size", 128))
    prefill = args.prefill or page
    if prefill % cfg.block_length:
        raise SystemExit("--prefill must be whole blocks")
    served = {}
    for case, patch in controls(family).items():
        served[case] = {"family": family, **{
            name: (traced_apart(family, name, patch) if patch
                   else getattr(family, name))
            for name in ("prefill_into_pages", "verify_step_paged")}}
    out_dir = os.path.join(ROOT, "chiprun_out", "check_blocks")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, config["model_id"] + ".jsonl"), "a") as log:
        for seed in (int(s) for s in args.seeds.split(",")):
            params = launcher.make_params(family, cfg, seed, mesh)

            for case in args.cases.split(","):
                t = time.monotonic()
                swap = None
                if case == "int8_weights":
                    check_limits.rounded_to_int8(params)

                    def swap():
                        params.clear()  # the rounded ones go first
                        params.update(launcher.make_params(family, cfg, seed,
                                                           mesh))

                judge = check_limits.like(
                    reference, lambda _none, hf, ids, **kw:
                    reference.forward(params, hf, ids, **kw))
                result = one_check(served[case], cfg, dict(params), config,
                                   spec, seed, page, judge, prefill,
                                   args.rounds, swap)
                line = {"model_id": config["model_id"], "tag": args.tag,
                        "device": devices[0].device_kind, "seed": seed,
                        "case": case, "seconds": time.monotonic() - t,
                        "sizes": {"rows": ROWS, "prefill_tokens": prefill,
                                  "rounds": args.rounds},
                        "result": result}
                print(json.dumps(line), flush=True)
                log.write(json.dumps(line) + "\n")
            del params
    return 0


if __name__ == "__main__":
    sys.exit(main())
