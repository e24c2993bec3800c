"""Gated delta-rule mixer ops (linear attention whose update reads the state
it writes): a chunked form for prefill and extend, a one-token step for
decode. docs/linear-attention.md has the derivation.

Per head, with keys of K numbers (L2-normalised by the caller), values of V
and a state S in R^{K x V}, float32, zero for a fresh sequence:

    S <- a_t S;   u_t = b_t (v_t - S^T k_t);   S <- S + k_t u_t^T;   o_t = S^T q_t

`a_t` = exp(g_t) in (0, 1] is the decay and `b_t` in (0, 2) the write
strength, both per head and token. The decay is ONE number a head (Olmo's
gated delta rule: `g` [.., H]) or one a KEY CHANNEL (Kimi Delta Attention:
`g` [.., H, K], `S <- Diag(a_t) S`, a scale of the state's rows); the rank
of `g` (of `alpha`) is a static choice and the scalar case traces the
program it always did. Unlike ops/ssm.py's recurrence the input
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
  position `lens - 1` whatever the padded length. With a decay a key
  channel the pair's factor is a sum over channels, A_ij = b_i sum_c k_ic
  k_jc e^{G_ic - G_jc}, and NO exponent is ever positive
  (`_pair_products`): e^{-G} alone overflows float32 inside one chunk.
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


SUB = 16  # rows of a sub-chunk of the vector-decay form (_pair_products)


def _pair_products(lefts, kc, cum, sub: int):
    """For each `left` [B, H, C, K] of `lefts`: P_ij = sum_c left_ic k_jc
    e^{G_ic - G_jc} for j <= i and 0 above the diagonal, [B, H, C, C], with
    `cum` [B, H, C, K] the chunk's cumulative g (falling). The factorisation
    (left e^G)(k e^-G) overflows; here every exponent is <= 0: the chunk is
    cut into sub-chunks of `sub` rows, a pair of two sub-chunks I > J is
    taken relative to G at I's first row (G_i - G_ref <= 0 for i in I,
    G_ref - G_j <= 0 for j before I: two factors, one product), and a pair
    inside one sub-chunk is summed with its difference G_i - G_j itself."""
    b, h, c, dk = kc.shape
    nb = c // sub

    def blocks(x):
        return x.reshape(b, h, nb, sub, dk)

    cum_b, k_b = blocks(cum), blocks(kc)
    inside = jnp.tril(jnp.ones((sub, sub), bool))[..., None]
    within = jnp.exp(jnp.where(
        inside, cum_b[..., :, None, :] - cum_b[..., None, :, :], -jnp.inf))
    eye = jnp.eye(nb, dtype=F32)[:, None, :, None]  # [nb, 1, nb, 1]
    if nb > 1:
        ref = cum_b[..., 0, :]  # [B, H, nb, K]: G at a sub-chunk's first row
        before = (jnp.arange(c)[None, :] < sub * jnp.arange(nb)[:, None])
        right = kc[:, :, None] * jnp.exp(jnp.where(
            before[..., None], ref[..., None, :] - cum[:, :, None], -jnp.inf))
        fall = jnp.exp(cum_b - ref[..., None, :])  # [B, H, nb, sub, K]
    out = []
    for left in lefts:
        l_b = blocks(left)
        diag = jnp.sum(l_b[..., :, None, :] * k_b[..., None, :, :] * within,
                       axis=-1)  # [B, H, nb, sub, sub]
        p = diag[..., None, :] * eye  # [B, H, nb, sub, nb, sub]
        if nb > 1:
            p = p + jnp.einsum("bhnrk,bhnjk->bhnrj", l_b * fall, right,
                               precision=_HI).reshape(b, h, nb, sub, nb, sub)
        out.append(p.reshape(b, h, c, c))
    return out


def delta_rule_chunked(q, k, v, g, beta, s0, lens, *, chunk: int = CHUNK):
    """q, k [B, T, H, K] (normalised, q scaled); v [B, T, H, V]; g [B, T, H]
    f32 the log of the decay (<= 0), or [B, T, H, K]: one a key channel;
    beta [B, T, H] f32; s0 [B, H, K, V] f32; lens [B]. Returns
    (o [B, T, H, V] f32, the state after position lens - 1 [B, H, K, V]
    f32)."""
    bt, t, h, dk = q.shape
    dv = v.shape[-1]
    vector = g.ndim == 4
    valid = (jnp.arange(t, dtype=lens.dtype)[None, :] < lens[:, None])[..., None]
    g = jnp.where(valid[..., None] if vector else valid, g.astype(F32), 0.0)
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

    sub = SUB if chunk % SUB == 0 else chunk

    def one_vector(s, inp):
        qc, kc, vc, gc, bc = inp  # as `one`'s, gc [B, H, C, K]
        qc, kc, vc = (x.astype(F32) for x in (qc, kc, vc))
        cum = jnp.cumsum(gc, axis=-2)  # G a channel, falling from 0
        kk, qk = _pair_products((kc, qc), kc, cum, sub)
        a = jnp.where(strict, bc[..., None] * kk, 0.0)
        fallen = jnp.exp(cum)  # e^{G_i}: what S_0 has lost by row i
        rhs = jnp.concatenate(
            [bc[..., None] * vc, bc[..., None] * fallen * kc], axis=-1)
        w = lax.linalg.triangular_solve(eye + a, rhs, left_side=True,
                                        lower=True, unit_diagonal=True)
        u = w[..., :dv] - jnp.einsum("bhck,bhkv->bhcv", w[..., dv:], s,
                                     precision=_HI)
        o = (jnp.einsum("bhck,bhkv->bhcv", fallen * qc, s, precision=_HI)
             + jnp.einsum("bhij,bhjv->bhiv", qk, u, precision=_HI))
        end = cum[..., -1:, :]  # [B, H, 1, K]
        s = (jnp.swapaxes(jnp.exp(end), -1, -2) * s
             + jnp.einsum("bhck,bhcv->bhkv", jnp.exp(end - cum) * kc, u,
                          precision=_HI))
        return s, o

    with jax.named_scope("kda_chunked" if vector else "delta_rule_chunked"):
        s, o = lax.scan(one_vector if vector else one, s0.astype(F32),
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


def _kda_decode_kernel(layer_ref, slot_ref, q_ref, k_ref, a_ref, beta_ref,
                       v_ref, s_ref, o_ref, out_ref, *, heads: int, dv: int):
    """`_delta_decode_kernel` with a decay a KEY CHANNEL: a scale of the
    tile's ROWS, so it runs down the sublanes as q and k do. The three
    arrive as they are computed, [H, K] a slot, and are turned HERE ([K, H]:
    a head's column down the state's sublanes) — turned in front of the
    kernel they cost a dozen small device operations a layer and step (and
    handed over as rows of H * K lanes, to be folded here, they cost MORE:
    the compiler then stages each in fast memory first; the compile for a
    described v5e counted 3,697, 3,293 and 3,767 operations a step); the
    write strength [1, H] and the value [1, H * V] arrive on lanes."""
    del layer_ref, slot_ref
    hb = _heads_per_block(heads, dv)
    width = hb * dv

    def along_lanes(cols, first):
        """cols [rows, H]: column first + j over the lanes of head j."""
        shape = (cols.shape[0], width)
        out = jnp.broadcast_to(cols[:, first + hb - 1:first + hb], shape)
        lane = lax.broadcasted_iota(jnp.int32, shape, 1)
        for j in range(hb - 2, -1, -1):
            out = jnp.where(lane < (j + 1) * dv,
                            cols[:, first + j:first + j + 1], out)
        return out

    q_cols, k_cols, a_cols = q_ref[...].T, k_ref[...].T, a_ref[...].T
    beta = beta_ref[...]
    for blk in range(heads // hb):
        lanes = pl.ds(blk * width, width)
        kc = along_lanes(k_cols, blk * hb)
        s = s_ref[:, lanes] * along_lanes(a_cols, blk * hb)
        u = along_lanes(beta, blk * hb) * (
            v_ref[:, lanes] - jnp.sum(s * kc, axis=0, keepdims=True))
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
    [B, H, V] f32, alpha [B, H] f32 (or [B, H, K]: a decay a key channel,
    `_kda_decode_kernel` under the name `kda_step`) and beta [B, H] f32; row
    i is slot i. Returns (pool, S^T q [B, H, V] f32)."""
    if interpret is None:
        interpret = ssm._interpret_default()  # one switch for both steps
    _, slots, dk, hv = pool.shape
    heads, dv = v.shape[1:]

    def row(i, layer, slot):
        return (slot[i], 0, 0)

    def row4(i, layer, slot):
        return (slot[i], 0, 0, 0)

    def state(i, layer, slot):
        return (layer[0], slot[i], 0, 0)

    if alpha.ndim == 3:  # a decay a key channel: nothing built in front
        kernel, name = _kda_decode_kernel, "kda_step"
        operands = (q, k, alpha, beta[:, None], v.reshape(slots, 1, hv))
        specs = [pl.BlockSpec((None, heads, dk), row)] * 3 + [
            pl.BlockSpec((None, 1, heads), row),
            pl.BlockSpec((None, 1, hv), row)]
    else:
        kernel, name = _delta_decode_kernel, "delta_rule_step"
        qk = jnp.stack([jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2)], axis=1)
        vec = jnp.stack([jnp.repeat(alpha, dv, axis=1),
                         jnp.repeat(beta, dv, axis=1), v.reshape(slots, hv)],
                        1)
        operands = (qk, vec)
        specs = [pl.BlockSpec((None, 2, dk, heads), row4),
                 pl.BlockSpec((None, 3, hv), row)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(slots,),
        in_specs=[*specs, pl.BlockSpec((None, None, dk, hv), state)],
        out_specs=[
            pl.BlockSpec((None, 1, hv), row),
            pl.BlockSpec((None, None, dk, hv), state),
        ],
    )
    o, pool = pl.pallas_call(
        functools.partial(kernel, heads=heads, dv=dv),
        out_shape=[jax.ShapeDtypeStruct((slots, 1, hv), F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        grid_spec=grid_spec,
        # the pool, behind the two scalars and the other operands
        input_output_aliases={2 + len(operands): 1},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=48 << 20),
        interpret=interpret,
        name=name,
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.arange(slots, dtype=jnp.int32), *operands, pool)
    return pool, o.reshape(slots, heads, dv)


def delta_rule_step(pool, layer, q, k, v, alpha, beta, *, slots=None,
                    live=None):
    """One token a row through the rule, the state pool [L, slots, K, H * V]
    f32 advanced in place at `layer`. q, k [B, H, K] (normalised, q scaled);
    v [B, H, V]; alpha, beta [B, H] (alpha [B, H, K]: a decay a key
    channel); `slots` [B] the rows' places in the pool (None: row i is slot
    i, and B is the pool's slot count); `live` [B] bool the rows to advance
    (None: all). Returns (o [B, H, V] f32, pool)."""
    bt, heads, _ = q.shape
    q, k, v, alpha, beta = (x.astype(F32) for x in (q, k, v, alpha, beta))
    vector = alpha.ndim == 3
    if live is not None:
        alpha = jnp.where(live[:, None, None] if vector else live[:, None],
                          alpha, 1.0)
        beta = jnp.where(live[:, None], beta, 0.0)
        k = jnp.where(live[:, None, None], k, 0.0)
    if slots is None and bt != pool.shape[1]:
        raise ValueError(f"{bt} rows for a state pool of {pool.shape[1]} "
                         "slots: say which slots they are")
    name = "kda_step" if vector else "delta_rule_step"
    if slots is None and _pallas_enabled():
        _traced[name] = "pallas:" + name
        pool, o = delta_rule_decode_step(pool, layer, q, k, v, alpha, beta)
        return o, pool
    _traced[name] = "xla"
    at = (layer,) if slots is None else (layer, slots)
    s = from_pool(pool[at], heads) * (  # [B, H, K, V]
        alpha[..., None] if vector else alpha[:, :, None, None])
    u = beta[:, :, None] * (v - jnp.einsum("bhkv,bhk->bhv", s, k,
                                           precision=_HI))
    s = s + k[:, :, :, None] * u[:, :, None, :]
    o = jnp.einsum("bhkv,bhk->bhv", s, q, precision=_HI)
    return o, pool.at[at].set(to_pool(s))
