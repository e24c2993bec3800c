"""The comparison behind `correct`, part (a): the engine's own serving
functions — prefill through the block table into a page pool, chunked
extends, then single-token decode steps through the paged cache, with the
kernels the engine dispatches to — against the plain float32 reference's
one whole-sequence forward pass, at the logits, on the configuration's real
widths and the run's seeded weights. Teacher-forced: both sides see the same
seeded token ids, because with random weights the largest logit flips on
rounding.

Runs in the launcher, before the engine's page pool is allocated, on a small
pool of its own (the reference's float32 expert weights need the room).
"""

from __future__ import annotations

import random

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import manifest


def rel_rms_err(got, want) -> float:
    """||got − want|| / ||want|| over all logits: the error as a share of the
    logits' own scale."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / max(np.sqrt(np.mean(want ** 2)), 1e-30))


def reference_forward(hf: dict):
    from benchmark.reference import REFERENCES

    kind = REFERENCES.get(hf.get("model_type", "llama"))
    if kind is None:
        raise ValueError(f"no plain reference for model_type "
                         f"{hf.get('model_type')!r}")
    return manifest.load_module("reference", kind).forward


def check(family, cfg, params, hf: dict, spec: dict, seed: int,
          page_size: int) -> dict:
    """spec: prefill_tokens, extend_chunks, extend_tokens, decode_steps,
    tolerance (see the configuration file for the reason behind it)."""
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

    rows = []  # (position whose next-token logits these are, logits [V])
    logits, cache_k, cache_v = family.prefill_into_pages(
        params, cfg, jnp.asarray(ids[None, :p0]), jnp.asarray([p0], np.int32),
        table, cache_k, cache_v, None)
    rows.append((p0 - 1, logits[0]))
    pos = p0
    for _ in range(chunks):
        logits, cache_k, cache_v = family.prefill_extend_pages(
            params, cfg, jnp.asarray(ids[None, pos:pos + ct]),
            jnp.asarray([ct], np.int32), jnp.asarray([pos], np.int32),
            table, cache_k, cache_v, None)
        pos += ct
        rows.append((pos - 1, logits[0]))
    for _ in range(steps):
        logits, cache_k, cache_v = family.decode_step_paged(
            params, cfg, jnp.asarray(ids[pos:pos + 1]),
            jnp.asarray([pos], np.int32), cache_k, cache_v, table, None,
            window=window)
        rows.append((pos, logits[0]))
        pos += 1
    got = np.stack([np.asarray(r, np.float32) for _, r in rows])
    del cache_k, cache_v

    want_all = reference_forward(hf)(params, hf, ids)
    want = np.asarray(want_all, np.float32)[[p for p, _ in rows]]
    errs = [rel_rms_err(g, w) for g, w in zip(got, want)]
    tol = float(spec["tolerance"])
    return {
        "ok": bool(max(errs) <= tol and np.isfinite(got).all()),
        "tolerance": tol,
        "max_rel_rms_err": max(errs),
        "prefill_rel_rms_err": errs[0],
        "decode_rel_rms_err": max(errs[-steps:]) if steps else None,
        "positions_compared": len(rows),
        "tokens": total,
    }
