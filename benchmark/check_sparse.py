"""The controls the limits of a configuration whose full layers attend only
over the cells a LEARNED INDEXER picks, beside sliding layers with a latent
ring a slot, a gate a head and a mixture a chip holds a share of
(`models/dots3_note.py`), are set between, beside those of `check_kda.py`
and its predecessors (whose loop and patches this takes): what is new with
this family, each as a program that must be refused, and the sound program
beside them, read TWICE. Every result is a JSON line on stdout and in
`chiprun_out/check_sparse/<model_id>.jsonl`.

    chiprun -- python3 benchmark/check_sparse.py --config <file> \
        --seeds 11,12,13 [--cases program,followed,dense_attention,...]

Cases:

  program            the program as it is, against the reference that
                     SELECTS FOR ITSELF: the reading `correct` sees
                     (benchmark/correctness.check cannot hand a reference the
                     cells). A cell at the top-k's last place decided the
                     other way by bf16 rounding is part of this reading.
  followed           the same program with its selection told to the
                     reference (`follow_cells`): the TIGHT reading, and with
                     it under `selection`: the program's index scores
                     against the reference's (relative RMS, the worst full
                     layer), whether each choice is the top-k of the
                     program's OWN scores with ties to the lower position,
                     how many cells a query the two selections disagree on,
                     and the widest margin of such a cell (the reference's
                     score less its top-k's last, in units of the scores'
                     error, as `routing_verdict` counts a router's flips).
  dense_attention    THE SELECTION IGNORED: every full layer attends over
                     its whole context. If it passes, the cell is not
                     measuring sparse attention.
  no_relu            the index score without its ReLU.
  topk_half          `index_topk` halved.
  window_less_one    `sliding_window_size` less one (512).
  no_gate            the gates of both kinds of layer left out.
  no_lora_scales     both latents of both kinds not rescaled.
  index_rope_pairs   the indexer's rotary on interleaved PAIRS where this
                     family rotates split halves.
  ring_zeroed        the rings zeroed before each extend: a chunk whose
                     window layers see nothing of what came before.
  unfollowed, unbiased_choice, int8_weights
                     as `check_config.py`, `check_limits.py` and
                     `check_kda.py` have them: the routing not followed; the
                     experts chosen without the bias; THE PRECISION CONTROL,
                     every matrix through int8 per output channel and back.

A tool for the PR that adds a configuration; the driver does not call it.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import re
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark import (  # noqa: E402
    check_band,
    check_config,
    check_hybrid,
    check_limits,
)

MATRICES = ("wq_a", "wq_b", "wkv_a", "wk_b", "wv_b", "w_gate", "wo", "wi_q",
            "wi_k", "wi_w", "wg", "wu", "wd", "router", "we_gate", "we_up",
            "we_down", "ws_gu", "ws_down")
RUN = re.compile(r"^r\d+_")  # a run's prefix (models/dots3_note.runs)
CASES = ("program,followed,dense_attention,no_relu,topk_half,"
         "window_less_one,no_gate,no_lora_scales,index_rope_pairs,"
         "ring_zeroed,unfollowed,unbiased_choice,int8_weights")


def matrices_to_int8(params: dict) -> None:
    """Every matrix of `params` through int8 and back, in place
    (check_limits.rounded_to_int8's rule, by name under a run's prefix)."""
    import jax

    from llmlb_tpu.quant.core import (
        dequantize_channelwise,
        quantize_channelwise,
    )

    trip = jax.jit(lambda w: dequantize_channelwise(
        *quantize_channelwise(w), dtype=w.dtype), donate_argnums=0)
    for name in list(params):
        if name in ("embed", "lm_head") or RUN.sub("", name) in MATRICES:
            params[name] = trip(params[name])


class Recfg:
    """`family` serving ANOTHER configuration than the one it is handed:
    `changes(cfg)` gives the fields replaced, in its serving functions and
    where its pool is made."""

    def __init__(self, family, changes):
        self._family = family

        def recfg(cfg):
            return dataclasses.replace(cfg, **changes(cfg))

        def served(fn):  # `routing` by name: benchmark/routing.observed asks
            def call(params, cfg, *args, routing: bool = False, **kw):
                return fn(params, recfg(cfg), *args, routing=routing, **kw)
            return call

        for name in check_limits.SERVING:
            setattr(self, name, served(getattr(family, name)))
        self.init_kv_pages = lambda cfg, *args, **kw: family.init_kv_pages(
            recfg(cfg), *args, **kw)

    def __getattr__(self, name):
        return getattr(self._family, name)


class Heard:
    """`family` with `selection=True` on its serving functions: what its
    full layers scored and chose is in `dots3_note.SELECTIONS` afterwards."""

    def __init__(self, family):
        self._family = family
        for name in check_limits.SERVING:
            setattr(self, name, self._telling(getattr(family, name)))

    def __getattr__(self, name):
        return getattr(self._family, name)

    @staticmethod
    def _telling(fn):
        def call(params, cfg, *args, routing: bool = False, **kw):
            return fn(params, cfg, *args, routing=routing, selection=True,
                      **kw)
        return call


def _scores_without_relu():
    """While a program is traced: the index scores sum_j w_j (q_j . k),
    every route through plain einsums."""
    import jax.numpy as jnp

    from llmlb_tpu.models import dots3_note
    from llmlb_tpu.ops import attention

    def plain(q, w, k):
        return jnp.einsum("bthd,bsd,bth->bts", q.astype(jnp.float32),
                          k.astype(jnp.float32), w.astype(jnp.float32))

    def paged(q, w, k_pages, layer, tables, window=None):
        ps = k_pages.shape[2]
        pages = attention._window_pages(tables, ps, window)
        k = attention.gather_kv_pages(k_pages, tables[:, :pages], layer=layer)
        return plain(q, w, k[..., k.shape[-1] - q.shape[-1]:])

    @contextlib.contextmanager
    def patch():
        with check_band.replaced(dots3_note, "index_scores",
                                 lambda _real: plain)(), \
                check_band.replaced(dots3_note, "paged_index_scores",
                                    lambda _real: paged)():
            yield

    return patch


def _index_rope_in_pairs(_real):
    import jax.numpy as jnp

    from llmlb_tpu.ops.rope import apply_rope

    def rotate(x, positions, inv_freq):
        r = 2 * inv_freq.shape[0]
        return jnp.concatenate(
            (apply_rope(x[..., :r], positions, inv_freq, True), x[..., r:]),
            axis=-1)

    return rotate


def variants(family) -> dict:
    """case -> the family with its serving functions changed."""
    from llmlb_tpu.models import deepseek_v3

    def rings_forgotten(ck, cv):
        return (ck._replace(state=ck.state * 0),
                cv._replace(state=cv.state * 0))

    return {
        "followed": Heard(family),
        "dense_attention": Recfg(family, lambda c: {"index_topk": 1 << 30}),
        "topk_half": Recfg(family,
                           lambda c: {"index_topk": c.index_topk // 2}),
        "window_less_one": Recfg(
            family, lambda c: {"sliding_window": c.sliding_window - 1}),
        "no_gate": Recfg(family,
                         lambda c: {"attn_gate": False, "swa_gate": False}),
        "no_lora_scales": Recfg(family, lambda c: {
            "q_lora_scale": 1.0, "kv_lora_scale": 1.0,
            "lora_rescale": False}),
        "no_relu": check_hybrid.Variant(family, patch=_scores_without_relu()),
        "index_rope_pairs": check_hybrid.Variant(
            family, patch=check_band.replaced(
                deepseek_v3, "apply_partial_rope", _index_rope_in_pairs)),
        "ring_zeroed": check_hybrid.Variant(family,
                                            before_extend=rings_forgotten),
        "unbiased_choice": check_limits.UnbiasedChoice(family),
    }


def stitched(heard: list, layers: int, total: int):
    """What a sequence's calls heard (`dots3_note.SELECTIONS`: a call's full
    layers in order, row 0 of each) as (scores [n_F, T, T] float32, chosen
    [n_F, T, T] bool), row t the query at position t over cells 0..T-1."""
    import numpy as np

    scores = np.zeros((layers, total, total), np.float32)
    chosen = np.zeros((layers, total, total), bool)
    assert len(heard) % layers == 0, (len(heard), layers)
    for i, (positions, scored, picked) in enumerate(heard):
        at = positions[0]
        keep = (at >= 0) & (at < total)
        width = min(total, scored.shape[-1])
        scores[i % layers, at[keep], :width] = scored[0][keep, :width]
        chosen[i % layers, at[keep], :width] = picked[0][keep, :width]
    return scores, chosen


def selection_verdict(scores, chosen, want_scores, top_k: int) -> dict:
    """The program's index `scores` and choice [n_F, T, T] against the
    reference's own scores `want_scores`: see the module's docstring."""
    import numpy as np

    layers, total, _ = scores.shape
    causal = np.tril(np.ones((total, total), bool))
    out = {"index_rel_rms_err": 0.0, "choice_is_own_topk": True,
           "disagreeing_cells_mean": 0.0, "disagreeing_cells_max": 0,
           "widest_disagreement_margin": 0.0}

    def own_choice(s):
        order = np.argsort(np.where(causal, -s.astype(np.float64), np.inf),
                           axis=-1, kind="stable")
        rank = np.argsort(order, axis=-1, kind="stable")
        return causal & (rank < top_k)

    for got, picked, want in zip(scores, chosen, want_scores):
        diff = (got - want)[causal].astype(np.float64)
        noise = float(np.sqrt(np.mean(diff ** 2)))
        out["index_rel_rms_err"] = max(out["index_rel_rms_err"], noise / max(
            float(np.sqrt(np.mean(want[causal].astype(np.float64) ** 2))),
            1e-30))
        out["choice_is_own_topk"] &= bool((own_choice(got) == picked).all())
        theirs = own_choice(want)
        apart = theirs != picked
        per_query = apart.sum(-1)
        out["disagreeing_cells_mean"] = max(out["disagreeing_cells_mean"],
                                            float(per_query.mean()))
        out["disagreeing_cells_max"] = max(out["disagreeing_cells_max"],
                                           int(per_query.max()))
        if apart.any():
            # the reference's score of its top-k's last cell, a query
            last = np.where(theirs, want, np.inf).min(-1, keepdims=True)
            margin = np.abs(want - last)[apart] / max(noise, 1e-30)
            out["widest_disagreement_margin"] = max(
                out["widest_disagreement_margin"], float(margin.max()))
    return out


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
    # one chip's tool: the selection is heard through ORDERED callbacks,
    # which a program laid out over several devices cannot make
    devices = resolve_backend()[:1]
    from llmlb_tpu.models import dots3_note, family_for
    from llmlb_tpu.parallel.mesh import build_mesh

    from benchmark import correctness, launcher, reference as refs

    cfg = launcher.build_cfg(config)
    family = family_for(cfg)
    reference = refs.module_for(config, args.base)
    mesh = build_mesh(launcher.mesh_config_for(cfg, len(devices)),
                      devices=devices)
    served_as = variants(family)
    page = int(config["engine"].get("kv_page_size", 128))
    total = (int(spec["prefill_tokens"]) + int(spec["decode_steps"])
             + int(spec.get("extend_chunks", 0))
             * int(spec.get("extend_tokens", 32)))
    full_layers = cfg.layers_of(dots3_note.FULL)
    out_dir = os.path.join(ROOT, "chiprun_out", "check_sparse")
    os.makedirs(out_dir, exist_ok=True)
    log_path = os.path.join(out_dir, config["model_id"] + ".jsonl")
    with open(log_path, "a") as log:
        for seed in (int(s) for s in args.seeds.split(",")):
            params = launcher.make_params(family, cfg, seed, mesh)

            def on_true_weights(params_, hf, ids, **kw):
                params_.clear()  # the rounded ones go first
                params_.update(launcher.make_params(family, cfg, seed, mesh))
                return reference.forward(params_, hf, ids, **kw)

            for case in args.cases.split(","):
                t = time.monotonic()
                note = {}
                served = served_as.get(case, family)
                judge = check_config.reference_for(case, reference, None)
                if case == "followed":
                    del dots3_note.SELECTIONS[:]

                    def told(params_, hf, ids, **kw):
                        import jax

                        jax.effects_barrier()  # every call has been heard
                        got, picked = stitched(dots3_note.SELECTIONS,
                                               full_layers, total)
                        seen: dict = {}
                        out = reference.forward(params_, hf, ids,
                                                follow_cells=picked,
                                                observe=seen, **kw)
                        note["selection"] = selection_verdict(
                            got, picked, seen["index_scores"],
                            cfg.index_topk)
                        return out

                    judge = check_limits.like(reference, told)
                elif case == "int8_weights":
                    matrices_to_int8(params)
                    judge = check_limits.like(reference, on_true_weights)
                result = correctness.check(served, cfg, params, config, spec,
                                           seed, page, judge)
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
