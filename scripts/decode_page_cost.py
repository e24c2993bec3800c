"""What a live page and a call cost the paged attention kernels, alone on the
chip: no model, no engine, no scheduler.

    chiprun -- python3 scripts/decode_page_cost.py

`paged_flash_decode` at the heads of the cells that run it (32 rows, pages of
128 cells, head size 128; 8 KV heads x 4 as Mistral-7B has them, 2 x 16 as
Nemotron-3-Nano's attention layers, 4 x 8 under a lower bound a row over a
band of 17 pages as Trinity-Mini's window layers call it), at every GROUP of
pages a grid step (1, 2, 4, 8; `decode_group`'s own pick is named beside
them): a sweep of 2, 4, 8 and 16 full pages a row, a straight line through it
(µs a call = `call_us` + `page_us` x live pages), the seconds ONE call takes
to trace and to lower at that group (`engine/compilelog`'s stages around a
fresh program of the work-list and one call: what a kernel change adds to
every program's build at every start, PERF.md §6 PR 53), and at the rule's
group one batch drawn as each cell draws its rows (`decode-saturated`: 32
rows somewhere between a prompt of 64-128 and 512 tokens more; `chat-paced`:
5 rows of 32 live, lognormal prompts and outputs; `reason-long-out`: 32 rows
between a prompt and 4,096 tokens more, the last 2,048 read). Then the two
decode kernels over pools without a head axis (`--only headless`, ~3 min):
`paged_latent_decode` at kanana-2-30b-a3b's 32 heads and at
longcat-flash-omni's 64 on a latent of 512 beside the rope's tile, and
`paged_flat_decode` at mimo-v2-5's 64 heads on 4 x 192 keys and 4 x 128
values, at groups of 1, 2 and 4 (flat: 3; the rule's named) — a sweep of 4,
12 and 24 full pages a row and its line, ragged rows of 1-9 pages and of
17-33 (µs a live page with the call in it), and `one_call` as above. Then
`paged_flash_extend` at the block family's call (32 rows x 8 queries, 4 KV
heads x 8, a table 8 pages wide, blocks of 4), a sweep of 1, 2, 4 and 8
pages, at q blocks of 4, 8, 16, 32, 64 and 128 queries (`--q-blocks` names
others), then a verify chunk's 8 queries and the q blocks about their
crossover at the dense cells' heads, in BOTH forms of the kernel's grid step
at each (`pallas_attention.extend_body`: "page", one masked product over the
stored page; "heads", a product a KV head): µs a live page, µs a call
before its first page, the form the kernel picks at that size, and the
crossover — the largest q block at which the masked form is the faster. A
form Mosaic refuses at a size (the masked one's scores at 128 queries) is
reported as refused.

A program is CALLS calls one after another, each on the last one's output, as
a decode program's layers are; the work-list is built once outside them, as a
decode step does. Two clocks: the device's own (the kernel's events on the
profiler's "XLA Ops" line, `device_us`) and the host's around the whole
program (`wall_us`, with whatever lies between two calls). The line is fitted
to the device's where the trace has it. Prints one JSON object and writes it
to `chiprun_out/decode_page_cost.json`. On the CPU (`JAX_PLATFORMS=cpu`) it
runs the interpreter at a tenth of the size and says so: a rehearsal of the
script, not a number (the seconds to trace and lower are the host's either
way, through the interpreter's lowering there).
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

ROWS, PAGE, HEAD_DIM, CALLS = 32, 128, 128, 16
SWEEP = (1, 2, 4, 8)  # pages a row, the extend tables
DECODE_SWEEP = (2, 4, 8, 16)  # pages a row, the decode tables
GROUPS = (1, 2, 4, 8)  # pages a grid step
BAND_WINDOW = 2048  # cells a row of the band's shape reads at most
DECODE_SHAPES = {  # name: (KV heads, queries a KV head, layers, table
    # width, band): a band's table is a row's own pages, which the
    # positions wrap around, read from a lower bound a row
    "mistral-7b K8xG4": (8, 4, 16, 16, False),
    "nemotron-3-nano K2xG16": (2, 16, 2, 16, False),  # the cell's 2 layers
    "trinity-mini band K4xG8": (4, 8, 12, BAND_WINDOW // PAGE + 1, True),
}
HEADLESS_SHAPES = {  # name: (kernel, query heads, layers, groups)
    "kanana-2-30b-a3b latent H32": ("paged_latent_decode", 32, 8, (1, 2, 4)),
    "longcat-flash-omni latent H64": ("paged_latent_decode", 64, 8,
                                      (1, 2, 4)),
    "mimo-v2-5 flat H64 on 4x192/4x128": ("paged_flat_decode", 64, 2,
                                          (1, 2, 3)),
}
HEADLESS_SWEEP = (4, 12, 24)  # pages a row: whole groups at 1, 2, 3 and 4
HEADLESS_RAGGED = {"1-9": (1, 9), "17-33": (17, 33)}  # pages a row, drawn
HEADLESS_TABLE = 34  # a table's width in pages
LATENT, ROPE_TILE, FLAT_KV, FLAT_K, FLAT_V = 512, 128, 4, 192, 128
EXTEND_SHAPES = {  # name: (KV heads, queries a KV head, layers, pool pages,
    # table width, block, q blocks): the block family's call at every q
    # block; a verify chunk's 8 queries at the dense cells' heads, then the
    # q blocks on both sides of `extend_body`'s threshold there
    "sdar-30b-a3b K4xG8": (4, 8, 7, 544, 8, 4, (4, 8, 16, 32, 64, 128)),
    "mistral-7b K8xG4": (8, 4, 16, 400, 8, 1, (8, 16, 32)),
    "nemotron-3-nano K2xG16": (2, 16, 2, 400, 8, 1, (8, 32, 64)),
}


def _draw_lens(rng, cell: str):
    """Row lengths as a cell's traffic leaves them in a steady window."""
    import numpy as np

    if cell == "decode-saturated":
        return rng.integers(64, 129, ROWS) + rng.integers(0, 513, ROWS)
    if cell == "reason-long-out":
        return rng.integers(64, 129, ROWS) + rng.integers(0, 4097, ROWS)
    lens = np.zeros(ROWS, np.int64)  # chat-paced
    live = rng.choice(ROWS, 5, replace=False)
    prompt = np.clip(np.exp(rng.normal(np.log(256), 0.9, 5)), 32, 1536)
    out = np.clip(np.exp(rng.normal(np.log(96), 0.6, 5)), 16, 384)
    lens[live] = (prompt + rng.random(5) * out).astype(np.int64)
    return lens


def _device_us(trace_dir: str, kernel: str):
    """(total µs, events) of the events named for the kernel on the
    device's "XLA Ops" line of the newest trace under `trace_dir` — the
    kernel's own, and what its wrapper builds in front of it under its name
    (`paged_flat_decode`'s widened queries: a second event a call); None
    where there is none."""
    import jax

    found = sorted(os.path.join(r, f) for r, _d, fs in os.walk(trace_dir)
                   for f in fs if f.endswith(".xplane.pb"))
    if not found:
        return None
    profile = jax.profiler.ProfileData.from_file(found[-1])
    spans = [e.duration_ns for p in profile.planes
             if (p.name or "").startswith("/device:TPU:")
             for ln in p.lines if ln.name == "XLA Ops"
             for e in ln.events if kernel in e.name]
    return (sum(spans) / 1e3, len(spans)) if spans else None


def _measure(program, args, kernel: str, reps: int) -> dict:
    import jax

    jax.block_until_ready(program(*args))  # compiled, and run once
    trace_dir = tempfile.mkdtemp(prefix="page-cost-")
    jax.profiler.start_trace(trace_dir)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = program(*args)
    jax.block_until_ready(out)
    wall = time.perf_counter() - t0
    jax.profiler.stop_trace()
    got = {"wall_us": wall / (reps * CALLS) * 1e6}
    traced = _device_us(trace_dir, kernel)
    if traced:  # µs a call, whatever events a call leaves under the name
        got["device_us"], got["events"] = (traced[0] / (reps * CALLS),
                                           traced[1])
    return got


def _fit(points: list[tuple[float, float]]) -> dict:
    """Least squares line through (live pages, µs a call)."""
    n = len(points)
    mx = sum(x for x, _ in points) / n
    my = sum(y for _, y in points) / n
    slope = (sum((x - mx) * (y - my) for x, y in points)
             / sum((x - mx) ** 2 for x, _ in points))
    return {"page_us": slope, "call_us": my - slope * mx}


def _tables(rng, pages_a_row, width: int, pool_pages: int):
    """Scattered distinct pool pages for the rows' live pages; the rest of a
    table row names page 0, as an engine's unallocated entries do."""
    import numpy as np

    tables = np.zeros((ROWS, width), np.int32)
    perm = rng.permutation(np.arange(1, pool_pages))
    at = 0
    for r, n in enumerate(pages_a_row):
        tables[r, :n] = perm[at:at + n]
        at += n
    return tables


def _operands(seed: int, q_shape: tuple, pool_shape: tuple):
    """Seeded bf16 queries, key pool and value pool."""
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)), 3)
    return [jax.random.normal(key, shape, jnp.bfloat16)
            for key, shape in zip(keys, (q_shape, pool_shape, pool_shape))]


def _line(sweep: list[dict]) -> dict:
    """The sweep's line, on the device's clock where every point has it."""
    clock = "device_us" if all("device_us" in s for s in sweep) else "wall_us"
    return {"clock": clock,
            **_fit([(s["live_pages"], s[clock]) for s in sweep])}


def _build_seconds(fn, args, builds: int = 3) -> dict:
    """Median seconds `fn` takes to trace and to lower, fresh each time, by
    engine/compilelog's stages (sums of JAX's own events: a nested trace
    counts in its caller's too, as in the engine's `setup.trace_lower_s`)."""
    import statistics

    import jax

    from llmlb_tpu.engine import compilelog

    seen = {"trace": [], "lower": []}
    for _ in range(builds):
        jax.clear_caches()
        before = compilelog.counters()
        jax.jit(fn).lower(*args)
        took = compilelog.summary(before)["seconds_total"]
        for stage in seen:
            seen[stage].append(took[stage])
    return {f"{stage}_s": statistics.median(v) for stage, v in seen.items()}


def decode_table(shape: str, reps: int, seed: int, small: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llmlb_tpu.ops import pallas_attention as pa
    from llmlb_tpu.ops.attention import BAND_DECODE

    num_kv, groups, layers, width, band = DECODE_SHAPES[shape]
    kernel = BAND_DECODE if band else "paged_flash_decode"
    pool_pages = ROWS * width + 1
    sweep_pages = DECODE_SWEEP
    if small:
        layers, width, sweep_pages = 2, (3 if band else 2), DECODE_SWEEP[:1]
        pool_pages = ROWS * width + 1
    window = (width - 1) * PAGE if band else None
    rng = np.random.default_rng(seed)
    q, k_pages, v_pages = _operands(
        seed, (ROWS, num_kv * groups, HEAD_DIM),
        (layers, pool_pages, PAGE, num_kv, HEAD_DIM))
    rule = pa.decode_group(PAGE * num_kv * 2 * HEAD_DIM, width)

    def call(group, calls):
        def program(q, k_pages, v_pages, tables, lens):
            kv_from = jnp.maximum(lens - window, 0) if band else None
            work = pa.decode_work_list(tables, lens, page_size=PAGE,
                                       kv_from=kv_from, group=group)
            for i in range(calls):
                q = pa.paged_flash_decode(
                    q, k_pages, v_pages, i % layers, tables, lens, work=work,
                    kv_from=kv_from, name=BAND_DECODE if band else None)
            return q

        return program

    def operands(lens):
        lens = np.asarray(lens)
        if band:  # every row holds its whole band
            tables = 1 + rng.permutation(ROWS * width).reshape(ROWS, width)
            pages = -(-lens // PAGE) - np.maximum(lens - window, 0) // PAGE
        else:
            lens = np.minimum(lens, width * PAGE)
            pages = -(-lens // PAGE)
            tables = _tables(rng, pages, width, pool_pages)
        return pages, (q, k_pages, v_pages, jnp.asarray(tables, jnp.int32),
                       jnp.asarray(lens, jnp.int32))

    def run(program, lens, group):
        pages, args = operands(lens)
        return {"live_pages": int(pages.sum()),
                "grid_steps": int((-(-pages // group)).sum()
                                  + (pages == 0).sum()),
                "live_rows": int((np.asarray(lens) > 0).sum()),
                **_measure(program, args, kernel, reps)}

    by_group = {}
    for group in GROUPS:
        program = jax.jit(call(group, CALLS))
        sweep = [{"pages_a_row": p, **run(program, np.full(ROWS, p * PAGE),
                                          group)}
                 for p in sweep_pages if p < width or not band]
        by_group[str(group)] = {
            "sweep": sweep, **({} if small else {"line": _line(sweep)}),
            "one_call": _build_seconds(
                call(group, 1), operands(np.full(ROWS, PAGE))[1])}
    cells = {}
    program = jax.jit(call(rule, CALLS))
    line = by_group.get(str(rule), {}).get("line")
    for cell in (("reason-long-out",) if band
                 else ("decode-saturated", "chat-paced")):
        if shape.startswith("nemotron") and cell == "chat-paced":
            continue  # no such cell
        got = run(program, _draw_lens(rng, cell), rule)
        if line:
            got["page_us_over_the_call"] = (
                (got[line["clock"]] - line["call_us"])
                / max(1, got["live_pages"]))
        cells[cell] = got
    return {"page_bytes": PAGE * num_kv * 2 * HEAD_DIM * 2,
            "rule_group": rule, "groups": by_group, "cells": cells}


def headless_table(shape: str, reps: int, seed: int, small: bool) -> dict:
    """The latent or the flat decode kernel at one cell's shape, at every
    group of its list."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llmlb_tpu.ops import pallas_attention as pa

    kernel, heads, layers, groups = HEADLESS_SHAPES[shape]
    width, sweep_pages, ragged = HEADLESS_TABLE, HEADLESS_SWEEP, HEADLESS_RAGGED
    if small:
        layers, width, sweep_pages = 2, 4, HEADLESS_SWEEP[:1]
        ragged = {"1-4": (1, 4)}
    pool_pages = ROWS * width + 1
    rng = np.random.default_rng(seed + 2)
    keys = jax.random.split(jax.random.PRNGKey((seed + 2) % (2 ** 31)), 4)
    if kernel == "paged_latent_decode":
        widths, q_widths = (LATENT, ROPE_TILE), (LATENT, ROPE_TILE)
    else:
        widths, q_widths = (FLAT_KV * FLAT_K, FLAT_KV * FLAT_V), (FLAT_K,)
    pools = [jax.random.normal(key, (layers, pool_pages, PAGE, w),
                               jnp.bfloat16)
             for key, w in zip(keys, widths)]
    queries = [jax.random.normal(key, (ROWS, heads, w), jnp.bfloat16)
               for key, w in zip(keys[2:], q_widths)]
    rule = pa.decode_group(PAGE * sum(widths), width)

    def call(group, calls):
        def program(queries, pools, tables, lens):
            work = pa.decode_work_list(tables, lens, page_size=PAGE,
                                       group=group)
            q = queries[0]
            for i in range(calls):  # each call on the last one's output
                if kernel == "paged_latent_decode":
                    q = pa.paged_latent_decode(
                        q, queries[1], *pools, i % layers, tables, lens,
                        scale=192 ** -0.5, work=work)  # the models' own
                else:
                    q = jnp.pad(pa.paged_flat_decode(
                        q, *pools, i % layers, tables, lens, num_kv=FLAT_KV,
                        work=work), ((0, 0), (0, 0), (0, FLAT_K - FLAT_V)))
            return q

        return program

    def operands(pages):
        tables = _tables(rng, pages, width, pool_pages)
        return (queries, pools, jnp.asarray(tables, jnp.int32),
                jnp.asarray(pages * PAGE, jnp.int32))

    def run(program, pages, group):
        got = {"live_pages": int(pages.sum()),
               "grid_steps": int((-(-pages // group)).sum()),
               **_measure(program, operands(pages), kernel, reps)}
        clock = "device_us" if "device_us" in got else "wall_us"
        got["us_a_live_page"] = got[clock] / got["live_pages"]
        return got

    by_group = {}
    for group in groups:
        program = jax.jit(call(group, CALLS))
        sweep = [{"pages_a_row": p, **run(program, np.full(ROWS, p), group)}
                 for p in sweep_pages if p <= width]
        by_group[str(group)] = {
            "sweep": sweep, **({} if small else {"line": _line(sweep)}),
            "ragged": {name: run(program, rng.integers(lo, hi + 1, ROWS),
                                 group)
                       for name, (lo, hi) in ragged.items()},
            "one_call": _build_seconds(
                call(group, 1), operands(np.full(ROWS, 1)))}
    return {"page_bytes": PAGE * sum(widths) * 2, "rule_group": rule,
            "groups": by_group}


def _extend_sweep(shape: str, body: str, queries: int, reps: int, seed: int,
                  small: bool) -> dict:
    """The sweep of one form of the extend kernels' grid step at one q
    block: `pallas_attention._paged_extend_call` with the form named, under
    the wrapper's name (the trace's row)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llmlb_tpu.ops import pallas_attention as pa

    num_kv, groups, layers, pool_pages, width, block, _ = \
        EXTEND_SHAPES[shape]
    if small:
        layers, pool_pages, width = 2, ROWS * 2 + 1, 2
    rng = np.random.default_rng(seed + 1)
    q, k_pages, v_pages = _operands(
        seed + 1, (ROWS, queries, num_kv * groups, HEAD_DIM),
        (layers, pool_pages, PAGE, num_kv, HEAD_DIM))

    @jax.jit
    def paged_flash_extend(q, k_pages, v_pages, layer, tables, starts, chunk):
        return pa._paged_extend_call(
            q, k_pages, v_pages, None, layer, tables, starts, chunk,
            block_q=pa.EXTEND_BLOCK_Q, interpret=None, block=block, body=body)

    @jax.jit
    def program(q, k_pages, v_pages, tables, starts, chunk):
        for i in range(CALLS):
            q = paged_flash_extend(q, k_pages, v_pages, i % layers, tables,
                                   starts, chunk)
        return q

    sweep = []
    for p in (SWEEP[:2] if small else SWEEP):
        tables = _tables(rng, np.full(ROWS, p), width, pool_pages)
        args = (q, k_pages, v_pages, jnp.asarray(tables),
                jnp.full((ROWS,), p * PAGE - queries, jnp.int32),
                jnp.full((ROWS,), queries, jnp.int32))
        sweep.append({"pages_a_row": p, "live_pages": ROWS * p,
                      "grid_steps": ROWS * width,
                      **_measure(program, args, "paged_flash_extend", reps)})
    return {"sweep": sweep, "line": _line(sweep)}


def extend_table(shape: str, q_blocks, reps: int, seed: int,
                 small: bool) -> dict:
    from llmlb_tpu.ops import pallas_attention as pa

    num_kv, groups, *_ = EXTEND_SHAPES[shape]
    table, faster = {}, {}
    for queries in q_blocks:
        row = {"chosen": pa.extend_body(min(pa.EXTEND_BLOCK_Q, queries),
                                        num_kv * groups, num_kv, PAGE)}
        for body in ("page", "heads"):
            try:
                row[body] = _extend_sweep(shape, body, queries, reps, seed,
                                          small)
            except Exception as e:  # Mosaic refuses the form at this size
                row[body] = {"refused": f"{type(e).__name__}: {e}"[:300]}
        if all("line" in row[body] for body in ("page", "heads")):
            faster[queries] = min(
                ("page", "heads"), key=lambda b: row[b]["line"]["page_us"])
        table[str(queries)] = row
    page_wins = [n for n, body in faster.items() if body == "page"]
    return {"q_blocks": table,
            "faster_by_page_us": {str(n): b for n, b in faster.items()},
            "crossover": {"largest_q_block_page_wins":
                          max(page_wins) if page_wins else None,
                          "rows_there": max(page_wins) * num_kv * groups
                          if page_wins else None}}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=30,
                    help="runs of a program of 16 calls, a measurement")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--only", choices=("decode", "headless", "extend"))
    ap.add_argument("--q-blocks", help="queries a row of the extend tables' "
                    "calls, for every shape (default: each shape's own)")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "decode_page_cost.json"))
    args = ap.parse_args()

    import jax

    device = jax.devices()[0]
    small = device.platform != "tpu"
    reps = 1 if small else args.reps
    out = {"device": device.device_kind, "platform": device.platform,
           "rows": ROWS, "page": PAGE, "head_dim": HEAD_DIM,
           "calls_a_program": CALLS, "reps": reps}
    if small:
        out["note"] = ("not a chip: the interpreter at a tenth of the size, "
                       "a rehearsal of the script and no number")
    if args.only in (None, "decode"):
        out["paged_flash_decode"] = {
            shape: decode_table(shape, reps, args.seed, small)
            for shape in DECODE_SHAPES}
    if args.only in (None, "headless"):
        out["paged_headless_decode"] = {
            shape: headless_table(shape, reps, args.seed, small)
            for shape in HEADLESS_SHAPES}
    if args.only in (None, "extend"):
        out["paged_flash_extend"] = {
            shape: extend_table(
                shape, [int(n) for n in args.q_blocks.split(",")]
                if args.q_blocks else spec[-1], reps, args.seed, small)
            for shape, spec in EXTEND_SHAPES.items()}
    text = json.dumps(out)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        f.write(text + "\n")
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
