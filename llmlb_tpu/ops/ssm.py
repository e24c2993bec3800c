"""State-space (Mamba-2, "SSD") mixer ops: a chunked scan for prefill and
extend, a one-token recurrence for decode.

Per head h of P channels, with its group's B and C vectors of N numbers
(heads // groups heads share a group) and a state S in R^{P x N}:

    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t (x) B_t        y_t = S_t C_t + D_h x_t

`dt` arrives after its softplus, `A` negative. What a sequence carries from
one call to the next is S (float32: the recurrence multiplies and adds
through every token) and, for the depthwise causal convolution in front of
it, the last `width - 1` rows of the convolution's input.

- `ssd_chunked` walks T positions in chunks of 128: inside a chunk the
  masked `C B^T` product (a [chunk, chunk] matrix a head, decays
  `exp(la_t - la_s)` of the chunk's own cumulative `la`), between chunks the
  carried state, in a `lax.scan` over the chunks. Positions at or past a
  row's length have dt = 0: they neither decay the state nor add to it, so
  the state returned is the one after position `lens - 1` whatever the
  padded length.
- `ssm_step` advances the stacked state pool [L, slots, H, P, N] by one
  token a row, in place at `layer`: `ssm_decode_step` (Pallas, the pool
  aliased in and out and addressed at (layer, slot)) on an unpartitioned
  TPU, the same arithmetic in `jax.numpy` elsewhere. Rows that are not
  `live` keep their state bit for bit (decay 1, input 0).
- `causal_conv` is the depthwise convolution with the carried rows in
  front, and says which rows to carry on; its bias and what follows it are
  the caller's (a gated short convolution has neither and is the mixer
  whole: models/lfm2_moe.py).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llmlb_tpu.ops.attention import _pallas_enabled

CHUNK = 128
F32 = jnp.float32
_HI = lax.Precision.HIGHEST  # products that feed the carried state


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def causal_conv(x, prev, w, b, lens, *, act=jax.nn.silu):
    """Depthwise causal convolution over time. x [B, T, C]; prev [B, W-1, C]
    the rows before position 0 (zeros for a fresh sequence); w [C, W] with
    w[:, W-1] on the current position; b [C], or None for a convolution
    without a bias; lens [B]; `act` what follows the convolution, or None
    for nothing (a Mamba layer's has both, a gated short convolution's
    neither: models/lfm2_moe.py). Returns (act(conv + b) [B, T, C], the rows
    to carry on [B, W-1, C]: the W-1 rows that end at position lens - 1,
    gathered by length so that a padded row carries its true tail)."""
    width = w.shape[-1]
    t = x.shape[1]
    padded = jnp.concatenate([prev.astype(x.dtype), x], axis=1)
    out = sum(padded[:, j:j + t].astype(F32) * w[:, j].astype(F32)
              for j in range(width))
    if b is not None:
        out = out + b.astype(F32)
    at = lens[:, None] + jnp.arange(width - 1, dtype=lens.dtype)[None, :]
    carry = jnp.take_along_axis(padded, at[:, :, None], axis=1)
    if act is not None:
        out = act(out)
    return out.astype(x.dtype), carry


def ssd_chunked(x, dt, a, b, c, d, s0, lens, *, chunk: int = CHUNK):
    """x [B, T, H, P]; dt [B, T, H] f32 (after softplus); a [H] f32 (< 0);
    b, c [B, T, G, N]; d [H]; s0 [B, H, P, N] f32; lens [B]. Returns
    (y [B, T, H, P] in x's type, the state after position lens - 1
    [B, H, P, N] f32)."""
    bt, t, h, p = x.shape
    g, n = b.shape[2:]
    r = h // g
    valid = jnp.arange(t, dtype=lens.dtype)[None, :] < lens[:, None]
    dt = jnp.where(valid[:, :, None], dt.astype(F32), 0.0)
    pad = -t % chunk
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                       for v in (x, dt, b, c))
    nc = (t + pad) // chunk

    def chunks(v):  # [B, nc * Q, ...] -> [nc, B, Q, ...]
        return jnp.moveaxis(v.reshape(bt, nc, chunk, *v.shape[2:]), 1, 0)

    causal = jnp.tril(jnp.ones((chunk, chunk), bool))

    def one(s, inp):
        xc, dtc, bc, cc = inp  # [B, Q, H, P], [B, Q, H], [B, Q, G, N] x 2
        la = jnp.cumsum(dtc * a, axis=1)  # [B, Q, H], falling from 0
        xg = xc.reshape(bt, chunk, g, r, p)
        # inside the chunk: y_q += sum_{s <= q} exp(la_q - la_s) dt_s (C_q . B_s) x_s
        cb = jnp.einsum("bqgn,bsgn->bgqs", cc, bc, preferred_element_type=F32)
        lah = jnp.moveaxis(la, 2, 1)  # [B, H, Q]
        decay = jnp.exp(jnp.where(causal, lah[:, :, :, None]
                                  - lah[:, :, None, :], -jnp.inf))
        w = (decay * jnp.moveaxis(dtc, 2, 1)[:, :, None, :]
             ).reshape(bt, g, r, chunk, chunk) * cb[:, :, None]
        y = jnp.einsum("bgrqs,bsgrp->bqgrp", w.astype(xc.dtype), xg,
                       preferred_element_type=F32)
        # from the chunks before: y_q += exp(la_q) C_q S
        y += (jnp.einsum("bqgn,bgrpn->bqgrp", cc.astype(F32), s, precision=_HI)
              * jnp.exp(la).reshape(bt, chunk, g, r, 1))
        # the state the chunk leaves
        end = la[:, -1:, :]  # [B, 1, H]
        xin = xg.astype(F32) * (dtc * jnp.exp(end - la)
                                ).reshape(bt, chunk, g, r, 1)
        s = (s * jnp.exp(end[:, 0]).reshape(bt, g, r, 1, 1)
             + jnp.einsum("bsgrp,bsgn->bgrpn", xin, bc.astype(F32),
                          precision=_HI))
        return s, y.reshape(bt, chunk, h, p)

    s, y = lax.scan(one, s0.astype(F32).reshape(bt, g, r, p, n),
                    tuple(map(chunks, (x, dt, b, c))))
    y = jnp.moveaxis(y, 0, 1).reshape(bt, nc * chunk, h, p)[:, :t]
    y = y + x[:, :t].astype(F32) * d.astype(F32)[:, None]
    return y.astype(x.dtype), s.reshape(bt, h, p, n)


def _step_inputs(x, dt, a, live):
    """(decay exp(dt A) [B, H], dt x [B, H, P]) of one token a row, f32; a
    row that is not live gets decay 1 and input 0: its state stays."""
    dt = dt.astype(F32)
    decay = jnp.exp(dt * a)
    dtx = dt[:, :, None] * x.astype(F32)
    if live is not None:
        decay = jnp.where(live[:, None], decay, 1.0)
        dtx = jnp.where(live[:, None, None], dtx, 0.0)
    return decay, dtx


def _tile_rows(h: int, p: int, groups: int) -> int:
    """The (head, channel) rows a turn of the kernel's loop takes: 512 (four
    [128, 128] tiles at once through the transpose unit: a turn waits for
    each of its two crossings, and at one tile a turn that wait paces the
    kernel) where whole heads fill them and a group's heads fill or divide
    them, else all of them at once."""
    rows, r = math.gcd(h * p, 512), h // groups
    hpt = rows // p
    whole = rows % p == 0 and (hpt % r == 0 or r % hpt == 0)
    return rows if whole else h * p


def _ssm_decode_kernel(layer_ref, slot_ref, decay_ref, dtx_ref, b_ref, c_ref,
                       s_ref, y_ref, o_ref):
    """One slot: S <- decay S + (dt x) (x) B, y = S C, `_tile_rows` (head,
    channel) rows a turn of the loop, the same turn whatever the heads and
    the groups. The decay is a scalar a head (SMEM) and multiplies its
    [P, N] rows as a splat; a group's B and C are sublane broadcasts over
    its heads' rows. Only dt x has to cross from lanes to sublanes (its
    strip of the row, broadcast and transposed), and the sum over N crosses
    back the same way: the product transposed so that N lies on sublanes,
    its vregs added, one sublane reduce a tile, and y arrives on the lanes
    it is written in."""
    del layer_ref
    slot = slot_ref[pl.program_id(0)]
    h, p, n = s_ref.shape
    groups = b_ref.shape[0]
    rows = _tile_rows(h, p, groups)
    hpt = rows // p  # heads a turn
    r = h // groups  # heads a group
    gpt = max(1, hpt // r)  # groups a turn

    def turn(t, carry):
        head = t * hpt
        lanes = pl.ds(pl.multiple_of(t * rows, rows), rows)

        def group_rows(ref):  # [rows, N]: each (head, channel) row's group's
            own = ref[pl.ds(head // r, gpt), :]
            return jnp.broadcast_to(own[:, None, :], (gpt, rows // gpt, n)
                                    ).reshape(rows, n)

        s = jnp.concatenate([s_ref[head + j] * decay_ref[slot, head + j]
                             for j in range(hpt)], axis=0)
        dtx = jnp.broadcast_to(dtx_ref[pl.ds(slot, 1), lanes], (n, rows)).T
        s = s + dtx * group_rows(b_ref)
        o_ref[pl.ds(head, hpt)] = s.reshape(hpt, p, n)
        y_ref[pl.ds(slot, 1), lanes] = jnp.sum((s * group_rows(c_ref)).T,
                                               axis=0, keepdims=True)
        return carry

    lax.fori_loop(0, h * p // rows, turn, 0)


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnames=("pool",))
def ssm_decode_step(pool, layer, decay, dtx, b, c, *, interpret=None):
    """The decode step's state update as one kernel over the STACKED pool
    [L, slots, H, P, N] f32, read and written in place at (layer, slot)
    (`input_output_aliases`): a slice of the stack handed to a kernel is
    copied, as a slice of the page pool was (PR 25). decay [B, H] (scalars,
    in SMEM), dtx [B, H, P] f32 (with y, all the rows' [B, H P] in one
    block for the whole call: a 3-D view of it made the compiler re-lay out
    the page pool, PERF.md section 6, PR 56), b and c [B, G, N]; row i is slot
    i. Returns (pool, S C [B, H, P] f32)."""
    if interpret is None:
        interpret = _interpret_default()
    _, slots, h, p, n = pool.shape
    groups = b.shape[1]

    def rows(i, *_):
        return (0, 0)

    def row(i, layer, slot, decay):
        return (slot[i], 0, 0)

    def state(i, layer, slot, decay):
        return (layer[0], slot[i], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(slots,),
        in_specs=[
            pl.BlockSpec((slots, h * p), rows),
            pl.BlockSpec((None, groups, n), row),
            pl.BlockSpec((None, groups, n), row),
            pl.BlockSpec((None, None, h, p, n), state),
        ],
        out_specs=[
            pl.BlockSpec((slots, h * p), rows),
            pl.BlockSpec((None, None, h, p, n), state),
        ],
    )
    y, pool = pl.pallas_call(
        _ssm_decode_kernel,
        out_shape=[jax.ShapeDtypeStruct((slots, h * p), F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        grid_spec=grid_spec,
        input_output_aliases={6: 1},  # the pool, behind the three scalars
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=48 << 20),
        interpret=interpret,
        name="ssm_decode_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.arange(slots, dtype=jnp.int32), decay.astype(F32),
      dtx.astype(F32).reshape(slots, h * p), b.astype(F32), c.astype(F32),
      pool)
    return pool, y.reshape(slots, h, p)


def ssm_step(x, dt, a, b, c, d, pool, layer, *, slots=None, live=None):
    """One token a row through the recurrence, the state pool [L, slots, H,
    P, N] f32 advanced in place at `layer`. x [B, H, P]; dt [B, H] (after
    softplus); a [H]; b, c [B, G, N]; d [H]; `slots` [B] the rows' places
    in the pool (None: row i is slot i, and B is the pool's slot count);
    `live` [B] bool the rows to advance (None: all). Returns (y [B, H, P]
    in x's type, pool)."""
    bt, h, p = x.shape
    g = b.shape[1]
    decay, dtx = _step_inputs(x, dt, a.astype(F32), live)
    if slots is None and bt != pool.shape[1]:
        raise ValueError(f"{bt} rows for a state pool of {pool.shape[1]} "
                         "slots: say which slots they are")
    if slots is None and _pallas_enabled():
        pool, sc = ssm_decode_step(pool, layer, decay, dtx, b, c)
    else:
        at = (layer,) if slots is None else (layer, slots)
        s = pool[at].reshape(bt, g, h // g, p, -1)
        s = (s * decay.reshape(bt, g, h // g, 1, 1)
             + dtx.reshape(bt, g, h // g, p, 1)
             * b.astype(F32)[:, :, None, None, :])
        sc = jnp.sum(s * c.astype(F32)[:, :, None, None, :], axis=-1
                     ).reshape(bt, h, p)
        pool = pool.at[at].set(s.reshape(bt, h, p, -1))
    y = sc + x.astype(F32) * d.astype(F32)[:, None]
    return y.astype(x.dtype), pool
