"""What a chunk's attention under a selection costs, alone on the chip: the
Pallas kernel (`pallas_attention.sparse_latent_extend`) against the blocked
einsums it stands for (`ops/attention._latent_extend_blocked`), no model, no
engine, no scheduler.

    chiprun -- python3 scripts/extend_select_cost.py

One row of 512 queries x 128 heads on a latent of 512 beside the rope's tile
(dots3-note-prev's full layers), the chunk the LAST 512 positions of a context
of 2k, 6k and 12k (`--contexts`), pools and a table as the cell's (pages of
128 cells, a table 136 pages wide, the rope pool's row 256 lanes wide with
the index key behind the rope cell), a selection drawn as the model draws it:
`topk_mask` of random scores, 2,048 of the cells a query sees (all of them
while it sees no more). At each context: the blocked einsums, then the kernel
at every q block (8, 16, 32 queries) and every group of pages a grid step (1,
2, 4, 8; `sparse_extend_blocks`' own pick is named), each checked against the
einsums' answer (`max_err`, bf16 outputs). A size Mosaic refuses is reported
as refused. (At PR 65 the kernel had a second form of its grid step, a
product a QUERY, [128, .] x [., cells], under its one mask row: 72-75% of the
MXU at 8 pages a step where the one product over the q block's rows reached
87-90%, and it was not kept; PERF.md §6 has both tables.)

A program is one call under `jax.jit`, run `--reps` times. Two clocks: the
device's own (the union of the events on the profiler's "XLA Ops" line over
the runs, `device_ms` a call) and the host's around the runs (`wall_ms`).
`mxu_share` is the call's matrix operations (2 x rows x live cells x (C + 128
+ C), the cells up to each query's position rounded up to the step's cells:
what a masked sweep multiplies) over the device's time at 197 TFLOP/s.
Prints one JSON object and writes it to
`chiprun_out/pr65/extend_select_cost.json`. On the CPU (`JAX_PLATFORMS=cpu`)
it runs the interpreter at a small size and says so: a rehearsal of the
script, not a number.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

PAGE, LATENT, ROPE_TILE, INDEX_KEY = 128, 512, 128, 128
PEAK_FLOPS = 197e12  # bf16, one v5e chip (Google Cloud documentation)
Q_BLOCKS = (8, 16, 32)
GROUPS = (1, 2, 4, 8)


def _busy_ms(trace_dir: str) -> float | None:
    """The device's busy time in the newest trace under `trace_dir` (the
    union of the events on its "XLA Ops" line: benchmark/trace.py's
    reduction), in ms; None where there is none."""
    import jax

    from benchmark import trace as trace_mod

    found = sorted(os.path.join(r, f) for r, _d, fs in os.walk(trace_dir)
                   for f in fs if f.endswith(".xplane.pb"))
    if not found:
        return None
    busy = trace_mod.reduce(jax.profiler.ProfileData.from_file(found[-1]),
                            window_s=0.0)["busy_s"]
    return None if busy is None else 1e3 * busy


def _measure(program, args, reps: int):
    import jax

    out = jax.block_until_ready(program(*args))  # compiled, and run once
    trace_dir = tempfile.mkdtemp(prefix="extend-cost-")
    jax.profiler.start_trace(trace_dir)
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(program(*args))
    wall = time.perf_counter() - t0
    jax.profiler.stop_trace()
    busy = _busy_ms(trace_dir)
    return out, {"wall_ms": 1e3 * wall / reps,
                 "device_ms": None if busy is None else busy / reps}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--contexts", default="2048,6144,12288")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "pr65", "extend_select_cost.json"))
    ns = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from llmlb_tpu.ops import attention
    from llmlb_tpu.ops import pallas_attention as kernels

    on_chip = jax.default_backend() == "tpu"
    # a rehearsal on the CPU: the interpreter at a small size
    t, heads, c_dim, ps, topk, table = (
        (512, 128, LATENT, PAGE, 2048, 136) if on_chip
        else (16, 16, 32, 8, 24, 8))
    contexts = ([int(x) for x in ns.contexts.split(",")] if on_chip
                else [32, 64])
    dtype = jnp.bfloat16
    pages = table + 4
    scale = (128 + 64) ** -0.5
    key = jax.random.PRNGKey(65)
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    c_pages = jax.random.normal(k1, (2, pages, ps, c_dim), dtype)
    r_pages = jax.random.normal(k2, (2, pages, ps, ROPE_TILE + INDEX_KEY),
                                dtype)
    q_abs = jax.random.normal(k3, (1, t, heads, c_dim), dtype)
    q_rope = attention._pad_last(
        jax.random.normal(k4, (1, t, heads, 64), dtype), ROPE_TILE)
    tables = jnp.asarray(np.random.default_rng(65).permutation(table) + 1,
                         jnp.int32)[None]
    cells = jnp.arange(table * ps, dtype=jnp.int32)
    lens = jnp.full((1,), t, jnp.int32)
    q_blocks = Q_BLOCKS if on_chip else (8, 16)

    report = {"device": {"platform": jax.devices()[0].platform,
                         "kind": jax.devices()[0].device_kind},
              "rehearsal": not on_chip,
              "shape": {"queries": t, "heads": heads, "latent": c_dim,
                        "page": ps, "table": table, "topk": topk},
              "picked": "q%d g%d" % kernels.sparse_extend_blocks(t, table),
              "contexts": {}}
    for context in contexts:
        positions = (context - t + jnp.arange(t, dtype=jnp.int32))[None]
        seen = cells[None, None, :] <= positions[:, :, None]
        chosen = jax.block_until_ready(jax.jit(
            lambda k, s: attention.topk_mask(
                jax.random.normal(k, s.shape, jnp.float32), s, topk)
        )(jax.random.fold_in(k5, context), seen))
        blocked = jax.jit(lambda qa, qr, c, r, tb, pos, sel:
                          attention._latent_extend_blocked(
                              qa, qr, c, r, 1, tb, pos, sel, scale))
        want, base = _measure(
            blocked, (q_abs, q_rope, c_pages, r_pages, tables, positions,
                      chosen), ns.reps)
        want = np.asarray(want, np.float32)
        entry = {"blocked_einsums": base, "kernel": {}}
        for block_q in q_blocks:
            for group in GROUPS:
                name = f"q{block_q} g{group}"
                step = group * ps
                live = np.minimum(
                    (np.asarray(positions[0]).reshape(-1, block_q).max(-1)
                     // step + 1) * step, table * ps)
                flops = 2 * heads * block_q * float(live.sum()) * (
                    2 * c_dim + ROPE_TILE)
                fn = jax.jit(
                    lambda qa, qr, c, r, tb, pos, sel, block_q=block_q,
                    group=group: kernels.sparse_latent_extend(
                        qa, qr, c, r, 1, tb, pos, lens, sel, scale=scale,
                        block_q=block_q, group=group))
                try:
                    got, cost = _measure(
                        fn, (q_abs, q_rope, c_pages, r_pages, tables,
                             positions, chosen), ns.reps)
                except Exception as e:  # Mosaic refused the size
                    entry["kernel"][name] = {
                        "refused": str(e).splitlines()[0][:300]}
                    continue
                cost["max_err"] = float(np.abs(
                    np.asarray(got, np.float32) - want).max())
                ms = cost["device_ms"] or cost["wall_ms"]
                cost["speedup"] = (base["device_ms"] or base["wall_ms"]) / ms
                if on_chip:
                    cost["mxu_share"] = flops / (ms * 1e-3) / PEAK_FLOPS
                entry["kernel"][name] = cost
                print(context, name, json.dumps(cost), file=sys.stderr,
                      flush=True)
        report["contexts"][str(context)] = entry
    os.makedirs(os.path.dirname(ns.out), exist_ok=True)
    with open(ns.out, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))


if __name__ == "__main__":
    main()
