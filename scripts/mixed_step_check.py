"""The mixed step against prefill-then-decode, on the chip, at a cell's sizes.

    chiprun -- python3 scripts/mixed_step_check.py \
        --config benchmark/configs/mistral-7b-l16.json

The launcher's `correct` (a) holds a program's `prefill_into_pages`,
`prefill_extend_pages` and `decode_step_paged` to the float32 reference
before the engine's pool exists; it does not reach `mixed_step_paged`, the
decode step whose pass carries an arrival's prompt
(models/llama._mixed_paged_impl, docs/scheduling.md "An arrival rides a
burst"). This stands in for it: at the configuration's own widths, depth and
precision, on the route the chip takes (Pallas kernels), a house of `--rows`
rows with contexts of 40-600 tokens — two of them not live, one the
arrival's — takes ONE arrival of 64-128 tokens both ways, and the logits
are compared: every decoding row's and the prompt's last position's, as
relative RMS error (benchmark/correctness.rel_rms_err) of the mixed step
against prefill-then-decode, which (a) holds to the reference. The two
tile their products differently (B + T tokens a product against T and B), so
bf16 rounds differently: expect 1e-3 to 1e-2, under the configuration's
`correctness.tolerance`; float32 on the CPU reads 1e-6
(tests/engine/test_mixed_step.py). The cells each path wrote are compared
the same way (`pool`). One JSON object on stdout and in --out; exit 1 where
a seed reads over the tolerance.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

SEEDS = tuple(range(2147483700, 2147483714))  # fourteen, past 2**31


def check(family, cfg, params, seed: int, *, rows: int, width: int,
          page_size: int) -> dict:
    """One seed's readings: the house and the arrival are drawn from it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.correctness import rel_rms_err

    rng = np.random.default_rng(seed)
    ppn = -(-(608 + width) // page_size)
    capacity = ppn * page_size
    cache_k, cache_v = family.init_kv_pages(cfg, rows * ppn + 1, page_size)
    tables = jnp.arange(1, rows * ppn + 1, dtype=jnp.int32).reshape(rows, ppn)
    lens = rng.integers(40, 600, (rows,)).astype(np.int32)
    arrival, *dead = rng.choice(rows, 3, replace=False).tolist()
    lens[arrival] = 0
    bucket = 512
    ids = rng.integers(8, cfg.vocab_size, (rows, 600)).astype(np.int32)
    for lo in range(0, rows, 8):  # the house: groups of eight, two chunks
        sl = slice(lo, lo + 8)
        first = np.minimum(lens[sl], bucket)
        _, cache_k, cache_v = family.prefill_into_pages(
            params, cfg, jnp.asarray(ids[sl, :bucket]), jnp.asarray(first),
            tables[sl], cache_k, cache_v)
        _, cache_k, cache_v = family.prefill_extend_pages(
            params, cfg, jnp.asarray(ids[sl, bucket:bucket + 128]),
            jnp.asarray(lens[sl] - first), jnp.asarray(first), tables[sl],
            cache_k, cache_v)
    n = int(rng.integers(width // 2, width + 1))
    prompt = np.zeros((1, width), np.int32)
    prompt[0, :n] = rng.integers(8, cfg.vocab_size, (n,))
    prompt_len = jnp.asarray([n], jnp.int32)
    last = jnp.asarray(rng.integers(8, cfg.vocab_size, (rows,)), jnp.int32)
    live = np.ones((rows,), np.bool_)
    live[dead] = False
    seq_lens = jnp.asarray(lens)

    copy = jax.tree.map(jnp.copy, (cache_k, cache_v))
    want_prompt, rk, rv = family.prefill_into_pages(
        params, cfg, jnp.asarray(prompt), prompt_len, tables[arrival][None],
        *copy)
    alone = live.copy()
    alone[arrival] = False
    want_rows, rk, rv = family.decode_step_paged(
        params, cfg, last, seq_lens.at[arrival].set(capacity - 1), rk, rv,
        tables, window=capacity, live=jnp.asarray(alone))
    got, mk, mv = family.mixed_step_paged(
        params, cfg, last, seq_lens, cache_k, cache_v, tables,
        jnp.asarray(prompt), prompt_len, jnp.asarray(arrival, jnp.int32),
        window=capacity, live=jnp.asarray(live))

    got, want_rows = np.asarray(got), np.asarray(want_rows)
    # the cells the two wrote: the prompt's pages and each row's open page
    written = np.unique(np.concatenate(
        [np.asarray(tables)[arrival, :-(-width // page_size)],
         np.asarray(tables)[np.arange(rows), np.minimum(lens, capacity - 1)
                            // page_size]]))
    pool = max(rel_rms_err(np.asarray(m[:, written], np.float32),
                           np.asarray(r[:, written], np.float32))
               for m, r in ((mk, rk), (mv, rv)))
    return {"seed": seed, "prompt_tokens": n, "arrival_row": arrival,
            "rows_decoding": int(alone.sum()),
            "rows": rel_rms_err(got[alone], want_rows[alone]),
            "prompt": rel_rms_err(got[arrival], np.asarray(want_prompt)[0]),
            "pool": pool}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config",
                    default="benchmark/configs/mistral-7b-l16.json")
    ap.add_argument("--preset", help="a preset of engine/presets.py instead "
                                     "(a CPU run: it only shows that it runs)")
    ap.add_argument("--seeds", type=int, nargs="+", default=list(SEEDS))
    ap.add_argument("--rows", type=int, default=32)
    ap.add_argument("--width", type=int, default=128)
    ap.add_argument("--page-size", type=int, default=128)
    ap.add_argument("--out", default="chiprun_out/mixed_step_check.json")
    args = ap.parse_args()

    from llmlb_tpu.startup import configure_compile_cache, resolve_backend

    configure_compile_cache()
    devices = resolve_backend()

    from benchmark import launcher
    from llmlb_tpu.engine.presets import get_preset
    from llmlb_tpu.models import family_for
    from llmlb_tpu.ops.attention import traced_routes
    from llmlb_tpu.parallel.mesh import build_mesh

    tolerance = 0.03
    if args.preset:
        cfg = get_preset(args.preset)
    else:
        with open(args.config) as f:
            config = json.load(f)
        cfg = launcher.build_cfg(config)
        tolerance = config["correctness"]["tolerance"]
    family = family_for(cfg)
    mesh = build_mesh(launcher.mesh_config_for(cfg, 1), devices=devices[:1])
    readings = []
    for seed in args.seeds:
        params = launcher.make_params(family, cfg, seed, mesh)
        readings.append(check(family, cfg, params, seed, rows=args.rows,
                              width=args.width, page_size=args.page_size))
        del params
    worst = {k: max(r[k] for r in readings) for k in ("rows", "prompt", "pool")}
    out = {"device": devices[0].device_kind,
           "config": args.preset or args.config, "tolerance": tolerance,
           "routes": traced_routes(), "seeds": len(readings),
           "rows_rel_rms_err": [min(r["rows"] for r in readings),
                                worst["rows"]],
           "prompt_rel_rms_err": [min(r["prompt"] for r in readings),
                                  worst["prompt"]],
           "pool_rel_rms_err": [min(r["pool"] for r in readings),
                                worst["pool"]],
           "ok": max(worst["rows"], worst["prompt"]) <= tolerance,
           "readings": readings}
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
