"""Gated delta-rule mixer ops (linear attention whose update reads the state
it writes): a chunked form for prefill and extend, a one-token step for
decode. docs/linear-attention.md has the derivation.

Per head, with keys of K numbers (L2-normalised by the caller), values of V
and a state S in R^{K x V}, float32, zero for a fresh sequence:

    S <- a_t S;   u_t = b_t (v_t - S^T k_t);   S <- S + k_t u_t^T;   o_t = S^T q_t

`a_t` = exp(g_t) in (0, 1] is the decay and `b_t` in (0, 2) the write
strength, both per head and token. Unlike ops/ssm.py's recurrence the input
`u_t` depends on the state, so neither its chunked scan nor its step kernel
serves here.

- `delta_rule_chunked` walks T positions in chunks of 64. Inside a chunk the
  inputs solve one unit lower-triangular system, (I + A) U = b (V - e^G K
  S_0) with A_ij = b_i e^{G_i - G_j} (k_i . k_j) for j < i and G the chunk's
  own cumulative g: ONE triangular solve a chunk and head (forward
  substitution, `lax.linalg.triangular_solve`), never a scan over tokens.
  The chunk's output is the carried state's part plus the masked `Q K^T`
  product over U; the state goes through a `lax.scan` over the chunks.
  Positions at or past a row's length get g = 0 and b = 0: they neither
  decay the state nor add to it, so the state returned is the one after
  position `lens - 1` whatever the padded length.
- `delta_rule_step` advances the stacked state pool by one token a row, in
  place at `layer`: `delta_rule_decode_step` (Pallas, the pool aliased in
  and out and addressed at (layer, slot)) on an unpartitioned TPU, the same
  arithmetic in `jax.numpy` elsewhere. A row that is not `live` gets a = 1,
  b = 0 and k = 0: its state stays bit for bit.

THE POOL'S LAYOUT: [L, slots, K, H * V] — the heads folded into the value
axis, so that the minor dimension (30 x 192 = 5,760 = 45 x 128 at the
published sizes) is whole lanes and the one before it (96) whole sublanes. A
float32 [.., 96, 192] is padded to 256 lanes in HBM: a third more bytes
moved every step and a third more pool. `to_pool` and `from_pool` are the
only places that know it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llmlb_tpu.ops import ssm
from llmlb_tpu.ops.attention import _pallas_enabled, _traced

CHUNK = 64
F32 = jnp.float32
_HI = lax.Precision.HIGHEST  # products that feed the carried state


def to_pool(s):
    """[.., H, K, V] -> the pool's [.., K, H * V]."""
    *lead, h, k, v = s.shape
    return jnp.moveaxis(s, -3, -2).reshape(*lead, k, h * v)


def from_pool(s, heads: int):
    """The pool's [.., K, H * V] -> [.., H, K, V]."""
    *lead, k, hv = s.shape
    return jnp.moveaxis(s.reshape(*lead, k, heads, hv // heads), -2, -3)


def delta_rule_chunked(q, k, v, g, beta, s0, lens, *, chunk: int = CHUNK):
    """q, k [B, T, H, K] (normalised, q scaled); v [B, T, H, V]; g [B, T, H]
    f32 the log of the decay (<= 0); beta [B, T, H] f32; s0 [B, H, K, V] f32;
    lens [B]. Returns (o [B, T, H, V] f32, the state after position
    lens - 1 [B, H, K, V] f32)."""
    bt, t, h, dk = q.shape
    dv = v.shape[-1]
    valid = (jnp.arange(t, dtype=lens.dtype)[None, :] < lens[:, None])[..., None]
    g = jnp.where(valid, g.astype(F32), 0.0)
    beta = jnp.where(valid, beta.astype(F32), 0.0)
    pad = -t % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    nc = (t + pad) // chunk

    def chunks(x):  # [B, nc * C, H, ...] -> [nc, B, H, C, ...]
        x = x.reshape(bt, nc, chunk, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 3, 2)

    tril = jnp.tril(jnp.ones((chunk, chunk), bool))
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    eye = jnp.eye(chunk, dtype=F32)

    def one(s, inp):
        qc, kc, vc, gc, bc = inp  # [B, H, C, K] x 2, [B, H, C, V], [B, H, C] x 2
        qc, kc, vc = (x.astype(F32) for x in (qc, kc, vc))
        cum = jnp.cumsum(gc, axis=-1)  # G, falling from 0
        decay = jnp.exp(jnp.where(tril, cum[..., :, None] - cum[..., None, :],
                                  -jnp.inf))  # e^{G_i - G_j}, j <= i
        kk = jnp.einsum("bhik,bhjk->bhij", kc, kc, precision=_HI)
        a = jnp.where(strict, bc[..., None] * decay * kk, 0.0)
        rhs = jnp.concatenate(
            [bc[..., None] * vc, (bc * jnp.exp(cum))[..., None] * kc], axis=-1)
        w = lax.linalg.triangular_solve(eye + a, rhs, left_side=True,
                                        lower=True, unit_diagonal=True)
        u = w[..., :dv] - jnp.einsum("bhck,bhkv->bhcv", w[..., dv:], s,
                                     precision=_HI)
        # o_i = e^{G_i} S_0^T q_i + sum_{j <= i} e^{G_i - G_j} (k_j . q_i) u_j
        qk = jnp.einsum("bhik,bhjk->bhij", qc, kc, precision=_HI) * decay
        o = (jnp.exp(cum)[..., None]
             * jnp.einsum("bhck,bhkv->bhcv", qc, s, precision=_HI)
             + jnp.einsum("bhij,bhjv->bhiv", qk, u, precision=_HI))
        # the state the chunk leaves
        end = cum[..., -1:]
        s = (jnp.exp(end)[..., None] * s
             + jnp.einsum("bhck,bhcv->bhkv",
                          jnp.exp(end - cum)[..., None] * kc, u,
                          precision=_HI))
        return s, o

    with jax.named_scope("delta_rule_chunked"):
        s, o = lax.scan(one, s0.astype(F32),
                        tuple(map(chunks, (q, k, v, g, beta))))
    # [nc, B, H, C, V] -> [B, T, H, V]
    o = jnp.moveaxis(jnp.moveaxis(o, 2, 3), 0, 1).reshape(
        bt, nc * chunk, h, dv)[:, :t]
    return o, s


def _heads_per_block(heads: int, dv: int) -> int:
    """Heads a kernel step takes at once: the fewest whose values fill whole
    lanes (2 x 192 = 3 x 128), else all of them."""
    for n in range(1, heads):
        if heads % n == 0 and (n * dv) % 128 == 0:
            return n
    return heads


def _delta_decode_kernel(layer_ref, slot_ref, qk_ref, vec_ref, s_ref, o_ref,
                         out_ref, *, heads: int, dv: int):
    """One slot: the four lines of the module's docstring over S [K, H * V].
    q and k arrive transposed ([2, K, H]: a head's key down the state's
    sublanes) and are broadcast along the head's V lanes; the decay, the
    write strength and the value arrive on lanes (vec [3, H * V]) and the
    output leaves the same way ([1, H * V])."""
    del layer_ref, slot_ref
    dk = s_ref.shape[0]
    hb = _heads_per_block(heads, dv)
    width = hb * dv
    lane = lax.broadcasted_iota(jnp.int32, (dk, width), 1)

    def along_lanes(cols, first):
        """cols [K, H]: column first + j over the lanes of head j."""
        out = jnp.broadcast_to(cols[:, first + hb - 1:first + hb], (dk, width))
        for j in range(hb - 2, -1, -1):
            out = jnp.where(lane < (j + 1) * dv,
                            cols[:, first + j:first + j + 1], out)
        return out

    q_cols, k_cols = qk_ref[0], qk_ref[1]
    for blk in range(heads // hb):
        lanes = pl.ds(blk * width, width)
        kc = along_lanes(k_cols, blk * hb)
        s = s_ref[:, lanes] * vec_ref[0:1, lanes]
        u = vec_ref[1:2, lanes] * (
            vec_ref[2:3, lanes] - jnp.sum(s * kc, axis=0, keepdims=True))
        s = s + kc * u
        out_ref[:, lanes] = s
        o_ref[:, lanes] = jnp.sum(s * along_lanes(q_cols, blk * hb), axis=0,
                                  keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnames=("pool",))
def delta_rule_decode_step(pool, layer, q, k, v, alpha, beta, *,
                           interpret=None):
    """The decode step's state update as one kernel over the STACKED pool
    [L, slots, K, H * V] f32, read and written in place at (layer, slot)
    (`input_output_aliases`): a slice of the stack handed to a kernel is
    copied, as a slice of the page pool was (PR 25). q, k [B, H, K] and v
    [B, H, V] f32, alpha and beta [B, H] f32; row i is slot i. Returns
    (pool, S^T q [B, H, V] f32)."""
    if interpret is None:
        interpret = ssm._interpret_default()  # one switch for both steps
    _, slots, dk, hv = pool.shape
    heads, dv = v.shape[1:]
    qk = jnp.stack([jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2)], axis=1)
    vec = jnp.stack([jnp.repeat(alpha, dv, axis=1),
                     jnp.repeat(beta, dv, axis=1), v.reshape(slots, hv)], 1)

    def row(i, layer, slot):
        return (slot[i], 0, 0)

    def row4(i, layer, slot):
        return (slot[i], 0, 0, 0)

    def state(i, layer, slot):
        return (layer[0], slot[i], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(slots,),
        in_specs=[
            pl.BlockSpec((None, 2, dk, heads), row4),
            pl.BlockSpec((None, 3, hv), row),
            pl.BlockSpec((None, None, dk, hv), state),
        ],
        out_specs=[
            pl.BlockSpec((None, 1, hv), row),
            pl.BlockSpec((None, None, dk, hv), state),
        ],
    )
    o, pool = pl.pallas_call(
        functools.partial(_delta_decode_kernel, heads=heads, dv=dv),
        out_shape=[jax.ShapeDtypeStruct((slots, 1, hv), F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        grid_spec=grid_spec,
        input_output_aliases={4: 1},  # the pool, behind the two scalars
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=48 << 20),
        interpret=interpret,
        name="delta_rule_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.arange(slots, dtype=jnp.int32), qk, vec, pool)
    return pool, o.reshape(slots, heads, dv)


def delta_rule_step(pool, layer, q, k, v, alpha, beta, *, slots=None,
                    live=None):
    """One token a row through the rule, the state pool [L, slots, K, H * V]
    f32 advanced in place at `layer`. q, k [B, H, K] (normalised, q scaled);
    v [B, H, V]; alpha, beta [B, H]; `slots` [B] the rows' places in the
    pool (None: row i is slot i, and B is the pool's slot count); `live`
    [B] bool the rows to advance (None: all). Returns (o [B, H, V] f32,
    pool)."""
    bt, heads, _ = q.shape
    q, k, v, alpha, beta = (x.astype(F32) for x in (q, k, v, alpha, beta))
    if live is not None:
        alpha = jnp.where(live[:, None], alpha, 1.0)
        beta = jnp.where(live[:, None], beta, 0.0)
        k = jnp.where(live[:, None, None], k, 0.0)
    if slots is None and bt != pool.shape[1]:
        raise ValueError(f"{bt} rows for a state pool of {pool.shape[1]} "
                         "slots: say which slots they are")
    if slots is None and _pallas_enabled():
        _traced["delta_rule_step"] = "pallas:delta_rule_step"
        pool, o = delta_rule_decode_step(pool, layer, q, k, v, alpha, beta)
        return o, pool
    _traced["delta_rule_step"] = "xla"
    at = (layer,) if slots is None else (layer, slots)
    s = from_pool(pool[at], heads) * alpha[:, :, None, None]  # [B, H, K, V]
    u = beta[:, :, None] * (v - jnp.einsum("bhkv,bhk->bhv", s, k,
                                           precision=_HI))
    s = s + k[:, :, :, None] * u[:, :, None, :]
    o = jnp.einsum("bhkv,bhk->bhv", s, q, precision=_HI)
    return o, pool.at[at].set(to_pool(s))
