"""The controls the limits of a configuration whose WINDOW attention layers
keep a BAND of pages a slot beside the global layers' pages are set between
(`models/afmoe.py`), beside those of `check_config.py`, `check_limits.py`,
`check_hybrid.py` and `check_window.py` (whose loop this repeats): what a
band with a lower bound, a gate on the attention's output, a norm on both
sides of a sub-layer, rotary by kind of layer, a scaled embedding and a
sigmoid-routed mixture with a shared expert can get wrong, each as a program
that must be refused, and the sound program beside them. Every result is a
JSON line on stdout and in `chiprun_out/check_band/<model_id>.jsonl`.

    chiprun -- python3 benchmark/check_band.py --config <file> \
        --seeds 11,12,13 [--cases program,no_window,...]

Cases:

  program          the program as it is
  interleaved_decode  SOUND, and must pass as `program` does: before each
                   extend call a decode step runs over the row with `live`
                   false, as the engine's burst steps a slot that is mid-way
                   through a chunked prefill. The band must not move.
  int8_weights     THE PRECISION CONTROL, as `check_limits.py` has it, over
                   the MATRICES by name (the norms and the choice bias
                   stay): each through int8 per output channel and back.
  no_window        the window left out: a band as long as the sequence.
  window_plus_one  the window off by one: a position sees the W before it.
  no_lower_mask    decode reads the whole of the oldest page: the lower
                   bound rounded down to its page.
  global_rotary    rotary on the global layers too.
  no_window_rotary  rotary left off the window layers.
  no_gate          the gate on the attention's output left out.
  no_attn_out_norm, no_mlp_out_norm  either second norm left out.
  no_qk_norm       the norm over each head of q and of k left out.
  no_embed_scale   sqrt(hidden) on the embedding left out.
  no_route_scale   `route_scale` left out of the mixture's weights.
  no_shared_expert  the shared expert left out of the mixture.
  bf16_router      the router's outputs rounded to bfloat16 before the
                   sigmoid, where float32 is stated.
  bf16_softmax     the attention kernels' scores rounded to bfloat16 before
                   the softmax, where float32 is stated.
  unfollowed, unbiased_choice, zeroed_chosen_expert
                   as `check_config.py` and `check_limits.py` have them; the
                   zeroed expert is the HELD expert the compared positions
                   chose most in the first mixture layer.

A tool for the PR that adds a configuration; the driver does not call it.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark import (  # noqa: E402
    check_config,
    check_hybrid,
    check_limits,
    check_window,
)

MATRICES = ("wq", "wk", "wv", "wgate", "wo", "wg", "wu", "wd", "router",
            "we_gate", "we_up", "we_down", "ws_gate", "ws_up", "ws_down")
PREFIXES = ("g_", "w_", "dense_")  # of the stacks; the mixtures' have none
CASES = ("program,interleaved_decode,int8_weights,no_window,window_plus_one,"
         "no_lower_mask,global_rotary,no_window_rotary,no_gate,"
         "no_attn_out_norm,no_mlp_out_norm,no_qk_norm,no_embed_scale,"
         "no_route_scale,no_shared_expert,bf16_router,bf16_softmax,"
         "unfollowed,unbiased_choice,zeroed_chosen_expert")


def matrices_to_int8(params: dict) -> None:
    """Every matrix of `params` through int8 and back, in place
    (check_limits.rounded_to_int8's rule, by name under a stack's prefix)."""
    import jax

    from llmlb_tpu.quant.core import (
        dequantize_channelwise,
        quantize_channelwise,
    )

    trip = jax.jit(lambda w: dequantize_channelwise(
        *quantize_channelwise(w), dtype=w.dtype), donate_argnums=0)
    for name in list(params):
        base = next((name[len(p):] for p in PREFIXES if name.startswith(p)),
                    name)
        if name in ("embed", "lm_head") or base in MATRICES:
            params[name] = trip(params[name])


def replaced(module, name: str, make):
    """A patch for check_hybrid.Variant: while a program is traced,
    `module.name` is `make(the real one)`."""

    @contextlib.contextmanager
    def patch():
        real = getattr(module, name)
        setattr(module, name, make(real))
        try:
            yield
        finally:
            setattr(module, name, real)

    return patch


def _kind_forced(kind: str):
    return lambda real: (lambda cfg, lp, x, positions, _kind:
                         real(cfg, lp, x, positions, kind))


def _gate_open(real):
    import jax.numpy as jnp

    # sigmoid(30) is 1 to float32's last place
    return lambda cfg, lp, x, attn, gate: real(cfg, lp, x, attn,
                                               jnp.full_like(gate, 30.0))


def _attn_out_unnormed(_real):
    import jax

    from llmlb_tpu.models import afmoe

    def gated_out(cfg, lp, x, attn, gate):
        b, t, _ = x.shape
        mixed = (attn.reshape(b, t, -1).astype(afmoe.F32)
                 * jax.nn.sigmoid(gate.astype(afmoe.F32))).astype(x.dtype)
        return x + afmoe._proj(lp, "wo", mixed)

    return gated_out


def _mlp_out_unnormed(real):
    return lambda cfg, live=None: [g._replace(out_norm="")
                                   for g in real(cfg, live)]


def _heads_unnormed(real):
    # q and k are the only 4-D values the family norms: [B, T, heads, D]
    return lambda x, w, eps=1e-6: x if x.ndim == 4 else real(x, w, eps)


def _lower_bound_on_its_page(real):
    def decode(q, k_pages, v_pages, layer, tables, kv_lens, kv_from, work=None):
        page = k_pages.shape[2]
        return real(q, k_pages, v_pages, layer, tables, kv_lens,
                    kv_from // page * page, work=work)

    return decode


def _shared_left_out(real):
    def mlp_fn(cfg, live=None):
        fn = real(cfg, live)
        return lambda lp, h, *a, **kw: fn(
            {**lp, "ws_down": lp["ws_down"] * 0}, h, *a, **kw)

    return mlp_fn


def _to_bf16(x):
    """`x` rounded to bfloat16's 8 bits of mantissa, in its own type: as an
    operation of its own, which no compiler folds away as it may a pair of
    converts."""
    import jax

    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _router_in_bf16(real):
    return lambda logits, *a, **kw: real(_to_bf16(logits), *a, **kw)


@contextlib.contextmanager
def softmax_in_bf16():
    """While a program is traced: the attention kernels' scores rounded to
    bfloat16 before the online softmax. The kernels' wrappers are jitted on
    their own and keep their traces by function and shapes, which a patch
    does not change: every trace of the process is dropped on the way in
    and out (`_clear_cache` of a wrapper drops its executables alone, and
    the probe then read, to the last digit, what the program traced before
    it had)."""
    import jax
    import jax.numpy as jnp

    from llmlb_tpu.ops import pallas_attention as pa

    real = pa._online_update
    # (Mosaic lowers no reduce_precision: a pair of converts inside a kernel)
    pa._online_update = lambda m, l, acc, idx, scores, v: real(
        m, l, acc, idx, scores.astype(jnp.bfloat16).astype(scores.dtype), v)
    jax.clear_caches()
    try:
        yield
    finally:
        pa._online_update = real
        jax.clear_caches()


def variants(family, total: int) -> dict:
    from llmlb_tpu.models import afmoe
    from llmlb_tpu.ops import moe

    def other(**change):
        return check_window.OtherConfig(
            family, lambda c: dataclasses.replace(c, **change))

    def patched(*a):
        return check_hybrid.Variant(family, patch=replaced(*a))

    return {
        "interleaved_decode": check_hybrid.Variant(family, step_live=False),
        "no_window": other(sliding_window=total + 1),
        "window_plus_one": check_window.OtherConfig(
            family, lambda c: dataclasses.replace(
                c, sliding_window=c.sliding_window + 1)),
        "no_lower_mask": patched(afmoe, "paged_band_decode",
                                 _lower_bound_on_its_page),
        "global_rotary": patched(afmoe, "_qkvg", _kind_forced(afmoe.WINDOW)),
        "no_window_rotary": patched(afmoe, "_qkvg",
                                    _kind_forced(afmoe.GLOBAL)),
        "no_gate": patched(afmoe, "_gated_out", _gate_open),
        "no_attn_out_norm": patched(afmoe, "_gated_out", _attn_out_unnormed),
        "no_mlp_out_norm": patched(afmoe, "_groups", _mlp_out_unnormed),
        "no_qk_norm": patched(afmoe, "rms_norm", _heads_unnormed),
        "no_embed_scale": other(mup_enabled=False),
        "no_route_scale": other(route_scale=1.0),
        "no_shared_expert": patched(afmoe, "_moe_mlp_fn", _shared_left_out),
        "bf16_router": patched(moe, "sigmoid_bias_routing", _router_in_bf16),
        "bf16_softmax": check_hybrid.Variant(family, patch=softmax_in_bf16),
        "unbiased_choice": check_limits.UnbiasedChoice(family),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--base", default=ROOT,
                    help="the directory of the manifest, for its references")
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--cases", default=CASES)
    ap.add_argument("--sizes", default="{}",
                    help="JSON laid over the file's correctness block")
    ap.add_argument("--tag", default="", help="goes into every line")
    args = ap.parse_args()
    with open(args.config) as f:
        config = json.load(f)
    spec = {**config["correctness"], **json.loads(args.sizes)}

    from llmlb_tpu.startup import configure_compile_cache, resolve_backend

    configure_compile_cache()
    devices = resolve_backend()
    import numpy as np

    from llmlb_tpu.models import family_for
    from llmlb_tpu.parallel.mesh import build_mesh

    from benchmark import correctness, launcher, reference as refs

    cfg = launcher.build_cfg(config)
    family = family_for(cfg)
    reference = refs.module_for(config, args.base)
    mesh = build_mesh(launcher.mesh_config_for(cfg, len(devices)),
                      devices=devices)
    served_as = variants(family, check_limits.compared_positions(spec)[-1] + 1)
    first, held = cfg.held_experts
    page = int(config["engine"].get("kv_page_size", 128))
    out_dir = os.path.join(ROOT, "chiprun_out", "check_band")
    os.makedirs(out_dir, exist_ok=True)
    log_path = os.path.join(out_dir, config["model_id"] + ".jsonl")
    with open(log_path, "a") as log:
        for seed in (int(s) for s in args.seeds.split(",")):
            params = launcher.make_params(family, cfg, seed, mesh)
            heard = []  # the program's choices [L, T, k], once told

            def hearing(params_, hf, ids, **kw):
                heard.append(np.asarray(kw["follow"]))
                return reference.forward(params_, hf, ids, **kw)

            def on_true_weights(params_, hf, ids, **kw):
                params_.clear()  # the rounded ones go first
                params_.update(launcher.make_params(family, cfg, seed, mesh))
                return reference.forward(params_, hf, ids, **kw)

            for case in args.cases.split(","):
                t = time.monotonic()
                note = {}
                with contextlib.ExitStack() as stack:
                    served = served_as.get(case, family)
                    judge = check_config.reference_for(case, reference, None)
                    if case == "program":
                        judge = check_limits.like(reference, hearing)
                    elif case == "int8_weights":
                        matrices_to_int8(params)
                        judge = check_limits.like(reference, on_true_weights)
                    elif case == "zeroed_chosen_expert":
                        if not heard:
                            raise SystemExit(f"{case}: run `program` first")
                        at = heard[0][0, check_limits.compared_positions(spec)]
                        mine = at[(at >= first) & (at < first + held)] - first
                        expert = int(np.bincount(mine.ravel(),
                                                 minlength=1).argmax())
                        note = {"zeroed": [0, expert], "read_by": int(
                            (at == first + expert).any(-1).sum())}
                        judge = check_limits.broken_leaf(
                            stack, params, reference, "we_down", (0, expert),
                            None)
                    result = correctness.check(served, cfg, params, config,
                                               spec, seed, page, judge)
                if case == "program" and heard:
                    chosen = heard[0]
                    note = {"chosen_held_share": float(
                        ((chosen >= first) & (chosen < first + held)).mean())}
                line = {"model_id": config["model_id"], "tag": args.tag,
                        "device": devices[0].device_kind, "seed": seed,
                        "case": case, "seconds": time.monotonic() - t,
                        **note, "result": result}
                print(json.dumps(line), flush=True)
                log.write(json.dumps(line) + "\n")
            del params
    return 0


if __name__ == "__main__":
    sys.exit(main())
