"""Every device program the engine runs: built, cached, warmed and called here.

`StepPrograms` alone knows how a model family is CALLED — its four entry
points, the keywords one family takes and another does not (`slot_ids`,
`num_slots`, `logits_from`) — and builds the jitted wrappers around them:
the decode burst (with or without the device grammar), the verify step
(legacy and fused), a block family's burst of block passes, the activation
programs. engine/scheduler.py decides WHICH program a step takes and with
what rows, and names no entry point. What a family IS: models/family.py.
"""

from __future__ import annotations

import logging
import threading
from functools import cached_property, partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from llmlb_tpu.ops.grammar import grammar_advance, grammar_bias
from llmlb_tpu.ops.sampling import sample_tokens, token_probability

log = logging.getLogger("llmlb_tpu.engine")

# Process-wide cache of the jit wrappers built below: a family's entry points
# are module-level jits every engine shares, and without this each engine
# would compile its scan/verify wrappers from scratch (a test suite's many
# short-lived CPU engines: a compile storm). Keyed by identity of the
# closed-over family and config (the values keep strong refs, so an id() is
# never recycled into an alias); grow-only, like jit's own executable cache.
# ONE lock: the prewarm thread and the step loop must share one wrapper per
# key (two for one signature would compile twice; one lets jax's internal
# compile lock dedup concurrent callers).
_PROGRAM_CACHE: dict[tuple, tuple] = {}
_PROGRAM_CACHE_LOCK = threading.Lock()


def _pack_step_counters(tokens, stats: dict, shapes: dict,
                        max_names: tuple) -> jnp.ndarray:
    """One int32 vector for the burst's ONE fetch: the token rows flattened,
    then the family's step counters (`stats`: name -> [steps, *shape]) in
    the order of their names, reduced over the burst's steps — summed, but
    for `max_names`, of which the largest is kept."""
    parts = [tokens.reshape(-1).astype(jnp.int32)]
    for name in sorted(shapes):
        per_step = stats[name].astype(jnp.int32)
        reduced = (jnp.max(per_step, axis=0) if name in max_names
                   else jnp.sum(per_step, axis=0))
        parts.append(reduced.reshape(-1))
    return jnp.concatenate(parts)


def _unpack_step_counters(flat: np.ndarray, rows: int, cols: int,
                          shapes: dict) -> tuple[np.ndarray, dict]:
    """The host's half of _pack_step_counters: (tokens [rows, cols],
    name -> int or nested list)."""
    at = rows * cols
    tokens = flat[:at].reshape(rows, cols)
    counters = {}
    for name in sorted(shapes):
        n = int(np.prod(shapes[name], dtype=np.int64))
        value = flat[at:at + n].reshape(shapes[name])
        counters[name] = value.tolist() if shapes[name] else int(value)
        at += n
    return tokens, counters


def _sample_chunk(logits, key, temps, top_ps, top_ks, seeds, mask, start_pos):
    """Per-position sampling for a verify chunk: [B, T, V] logits sampled as
    B*T independent rows with each slot's params repeated per position and
    the seed fold stepped by GLOBAL position (start + offset) — so a seeded
    row draws the exact same key at sequence position p whether p was
    reached by plain decode or inside a verify chunk (spec on/off produce
    bit-identical seeded streams). `mask` is an optional [B*T, V] additive
    grammar bias (per-position FSM lookahead rows)."""
    b, t, v = logits.shape
    flat = logits.reshape(b * t, v)

    def rep(x):
        return jnp.repeat(x, t)

    steps = (start_pos[:, None]
             + jnp.arange(t, dtype=jnp.int32)[None, :]).reshape(-1)
    toks = sample_tokens(flat, key, rep(temps), rep(top_ps), rep(top_ks),
                         mask, rep(seeds), steps)
    return toks.reshape(b, t)


@partial(jax.jit, donate_argnames=("state",))
def _activate_rows(logits, key, temps, top_ps, top_ks, seeds, lens, slot_ids,
                   bias, lora_rows, state):
    """Activation of one prefilled group as ONE program: split the engine's
    key, sample each row's first token from the prefill's `logits`
    [padded, V], and scatter the group's rows into the per-slot arrays of
    `state` (temps, top_ps, top_ks, seeds, seq_lens, last_tokens, lora_idx
    — donated). jit keys it by what it is handed: the padded group size,
    whether a grammar `bias` [padded, V] is present, whether the engine has
    adapters (`lora_rows`; without them lora_idx passes through). Padding
    rows repeat the last real row, so their duplicate scatters write
    identical values. Returns (new key, firsts [padded], new state)."""
    key, sk = jax.random.split(key)
    # steps = lens - 1: decode dispatches sample with the PRE-increment
    # seq_len, so the first decode token uses step = prompt_len — the
    # activation sample must fold a DIFFERENT step or a seeded request's
    # first two tokens would draw from the same per-row key.
    firsts = sample_tokens(logits, sk, temps, top_ps, top_ks, bias, seeds,
                           lens - 1)
    rows = (temps, top_ps, top_ks, seeds, lens, firsts, lora_rows)
    return key, firsts, tuple(
        arr if row is None else arr.at[slot_ids].set(row)
        for arr, row in zip(state, rows)
    )


def _sample_block(logits, key, temps, top_ps, top_ks, seeds, lens, n_masked):
    """Per-position sampling of a block pass: [S, B, V] logits as S*B rows,
    each slot's params repeated per position; returns the sampled ids and
    their probabilities (ops/sampling.token_probability), both [S, B]. A
    seeded row folds (absolute position, masks left in its block): the same
    key whatever rows share the batch and whether or not the block was
    started over after a park, and another key for each pass of a block."""
    s, b, v = logits.shape
    flat = logits.reshape(s * b, v)

    def rep(x):
        return jnp.repeat(x, b)

    steps = ((lens[:, None] + jnp.arange(b, dtype=jnp.int32)[None, :])
             * (b + 1) + n_masked[:, None]).reshape(-1)
    ids = sample_tokens(flat, key, rep(temps), rep(top_ps), rep(top_ks),
                        None, rep(seeds), steps)
    conf = token_probability(flat, ids, rep(temps))
    return ids.reshape(s, b), conf.reshape(s, b)


@partial(jax.jit, donate_argnames=("state",))
def _activate_block_rows(slot_ids, rows, state):
    """Activation of one prefilled group of a block family as ONE program:
    scatter the group's rows into the per-slot arrays of `state` (donated).
    Nothing is sampled: a block family's first tokens come from its first
    block's passes. Padding rows repeat the last real row."""
    return tuple(arr.at[slot_ids].set(row) for arr, row in zip(state, rows))


class StepPrograms:
    """The device programs of ONE engine: one family module, one
    configuration, one mesh, one burst length."""

    activate_rows = staticmethod(_activate_rows)
    activate_block_rows = staticmethod(_activate_block_rows)

    def __init__(self, module, cfg, mesh, *, decode_burst: int,
                 max_draft_tokens: int, num_slots: int, slot_capacity: int,
                 eos_id: int):
        self.module, self.cfg, self.mesh = module, cfg, mesh
        family = module.FAMILY
        self.decode_burst, self.eos_id = decode_burst, eos_id
        self.max_draft_tokens = max_draft_tokens
        self.num_slots, self.slot_capacity = num_slots, slot_capacity
        self.block = int(family.block_length(cfg))
        self.state_per_slot = family.state_slot_bytes is not None
        # Step counters the family computes on the device, name -> shape: a
        # burst carries them out in the fetch it already makes
        self.counter_shapes: dict[str, tuple] = family.step_counters(cfg)
        self._counter_max = tuple(
            name for name in self.counter_shapes
            if family.counters[name].reduce == "max")
        # this engine's programs, (kind, k, window, grammar) -> program
        self.cache: dict[tuple, Callable] = {}

    def fresh_kv_pool(self, num_pages: int, page_size: int, quantized: bool):
        """A zeroed K and V page pool on the mesh (with a state per slot
        beside it where the family keeps one)."""
        slots = {"num_slots": self.num_slots} if self.state_per_slot else {}
        ck, cv = self.module.init_kv_pages(self.cfg, num_pages, page_size,
                                           quantized=quantized, **slots)
        ck_sh, cv_sh = self.module.kv_pages_shardings(
            self.cfg, self.mesh, quantized=quantized)
        return jax.device_put(ck, ck_sh), jax.device_put(cv, cv_sh)

    def _slots_kw(self, slot_ids) -> dict:
        """A prefill call's rows' slots, for a family with state per slot."""
        return ({"slot_ids": jnp.asarray(slot_ids, jnp.int32)}
                if self.state_per_slot else {})

    def prefill(self, params, ids, lens, tables, cache_k, cache_v, *,
                slot_ids, lora_idx=None):
        """One-shot prefill of a group of same-bucket prompts in the slots
        `slot_ids`. Returns (logits, cache_k, cache_v, *stats)."""
        return self.module.prefill_into_pages(
            params, self.cfg, ids, lens, tables, cache_k, cache_v, self.mesh,
            lora_idx=lora_idx, **self._slots_kw(slot_ids))

    def extend(self, params, ids, chunk_lens, start_pos, tables, cache_k,
               cache_v, *, slot_ids, lora_idx=None):
        """One chunk of a prompt behind what its slot's pages hold already.
        Returns (logits, cache_k, cache_v, *stats)."""
        return self.module.prefill_extend_pages(
            params, self.cfg, ids, chunk_lens, start_pos, tables, cache_k,
            cache_v, self.mesh, lora_idx=lora_idx,
            **self._slots_kw(slot_ids))

    def decode_step(self, params, last, lens, cache_k, cache_v, tables, *,
                    window: int, live, lora_idx=None):
        """The legacy single step: (logits, cache_k, cache_v, *stats)."""
        return self.module.decode_step_paged(
            params, self.cfg, last, lens, cache_k, cache_v, tables,
            self.mesh, window=window, lora_idx=lora_idx, live=live)

    @cached_property
    def context_parallel_prefill(self) -> Callable:
        """(params, ids, lens) -> (logits, k_all, v_all): one-shot
        ring-attention prefill of a long prompt over the mesh's sp axis (a
        family whose record says `context_parallel_prefill`)."""
        return self.module.make_context_parallel_prefill(self.cfg, self.mesh)

    def _program(self, kind: str, k: int, window: int, grammar: bool,
                 shared: tuple, build: Callable) -> Callable:
        """Get or build this engine's program `(kind, k, window, grammar)`,
        through _PROGRAM_CACHE under `shared` (kind, extra): with the
        family, cfg and mesh that key is everything the trace closes over
        (array shapes go through jit's own shape-keyed cache per call)."""
        fn = self.cache.get((kind, k, window, grammar))
        if fn is None:  # a dispatch's path is the one lookup above
            key = (shared[0], id(self.module), id(self.cfg), self.mesh,
                   *shared[1])
            with _PROGRAM_CACHE_LOCK:
                hit = _PROGRAM_CACHE.get(key)
                if hit is None:
                    hit = _PROGRAM_CACHE[key] = (build(), self.module,
                                                 self.cfg)
                fn = self.cache[(kind, k, window, grammar)] = hit[0]
        return fn

    def unpack(self, flat: np.ndarray, rows: int):
        """A fetched burst on the host: (tokens [rows, SLOTS], the family's
        step counters of the burst or None where it has none)."""
        if not self.counter_shapes:
            return flat, None
        return _unpack_step_counters(flat, rows, self.num_slots,
                                     self.counter_shapes)

    def decode_many(self, window: int, grammar: bool = False) -> Callable:
        """The decode burst of this engine for a context-window bucket."""
        k = self.decode_burst
        return self._program(
            "decode_many", k, window, grammar,
            ("decode_many_gram" if grammar else "decode_many", (k, window)),
            lambda: self._build_decode_many(k, window, grammar))

    def _build_decode_many(self, k: int, window: int,
                           grammar: bool) -> Callable:
        """Jit a k-step decode: lax.scan feeds each step's sampled tokens
        back into the next ON DEVICE, so the host syncs once per k tokens.
        Sampling params and block tables are scan-invariant (the scheduler
        pre-allocates every page the burst will write); the caches are
        donated. `live`: the rows the dispatch emits for — the attention
        kernel walks their pages and no other row's. A family's step
        counters ride behind the tokens in the one array fetched.

        Under `grammar` (static) each step gathers the sampling bias from
        the device grammar table and advances the per-row cursor on the
        sampled token, so constrained slots ride the burst. Free rows carry
        cursor 0 (the all-zero row): + 0.0 everywhere, the unconstrained
        path bit for bit. Without it: no table, no cursor in the carry."""
        module, cfg, mesh = self.module, self.cfg, self.mesh
        shapes, max_names = self.counter_shapes, self._counter_max

        def many(params, last, lens, cache_k, cache_v, tables,
                 temps, top_ps, top_ks, seeds, key, live, gram_table=None,
                 gram_state=None, lora_idx=None):
            keys = jax.random.split(key, k)

            def body(carry, step_key):
                last, lens, gs, ck, cv = carry
                logits, ck, cv, *stats = module.decode_step_paged(
                    params, cfg, last, lens, ck, cv, tables, mesh,
                    window=window, lora_idx=lora_idx, live=live,
                )
                bias = grammar_bias(gram_table, gs) if grammar else None
                toks = sample_tokens(logits, step_key, temps, top_ps,
                                     top_ks, bias, seeds, lens)
                if grammar:
                    gs = grammar_advance(gram_table, gs, toks)
                return (toks, lens + 1, gs, ck, cv), (toks, stats)

            first_in = last  # pre-burst tokens: pending first emissions
            (last, lens, _, cache_k, cache_v), (toks, stats) = jax.lax.scan(
                body, (last, lens, gram_state, cache_k, cache_v), keys
            )
            toks = jnp.concatenate([first_in[None, :], toks], axis=0)
            if shapes:
                toks = _pack_step_counters(toks, stats[0], shapes, max_names)
            return last, lens, cache_k, cache_v, toks

        return jax.jit(many, donate_argnums=(3, 4))

    def admit_many(self, window: int) -> Callable:
        """The decode burst whose first step carries ONE arrival's prompt
        (a family whose record says `mixed_step`), for a context-window
        bucket and the engine's one prompt width."""
        k = self.decode_burst
        return self._program(
            "admit_many", k, window, False, ("admit_many", (k, window)),
            lambda: self._build_admit_many(k, window))

    def _build_admit_many(self, k: int, window: int) -> Callable:
        """Jit a k-step burst that ADMITS: step 0 is the family's mixed step
        — the rows' one token each and the arrival's whole prompt in one
        pass over the weights (models/llama._mixed_paged_impl) — then the
        scan of the k - 1 decode steps left, the arrival's row among them.
        The arrival's sampling row, its length and its slot are scattered
        into the per-slot arrays HERE (what _activate_rows does in a
        dispatch of its own), so an admission is one call where it was
        three. Traced as `admit_many`: `jit_many` stays the pure decode
        burst for whoever reads a device trace by program.

        `arrival` int32 [4]: (slot, prompt tokens n, top_k, seed);
        `arrival_f` float32 [2]: (temperature, top_p); `prompt_ids` [1, T].
        The row enters with n - 1 tokens: to the sampler and to the length
        counter its step 0 is a decode step that took T tokens in place of
        one — it writes up to cell n - 1, samples with the fold n - 1 (the
        activation's) and leaves n, so all k steps of the burst are alike
        and the fetch brings the row k tokens, the first of them the
        request's first. Returns the state (last tokens, lengths, the four
        sampling arrays), the caches and the burst's one array to fetch, as
        _build_decode_many's — without step counters: a family that counts
        on the device brings them when it brings its mixed step."""
        module, cfg, mesh = self.module, self.cfg, self.mesh

        def admit_many(params, last, lens, cache_k, cache_v, tables,
                       temps, top_ps, top_ks, seeds, key, live,
                       prompt_ids, arrival, arrival_f):
            keys = jax.random.split(key, k)
            slot, n = arrival[0], arrival[1]
            temps = temps.at[slot].set(arrival_f[0])
            top_ps = top_ps.at[slot].set(arrival_f[1])
            top_ks = top_ks.at[slot].set(arrival[2])
            seeds = seeds.at[slot].set(arrival[3])
            lens = lens.at[slot].set(n - 1)
            first_in = last  # pre-burst tokens: pending first emissions

            logits, cache_k, cache_v = module.mixed_step_paged(
                params, cfg, last, lens, cache_k, cache_v, tables,
                prompt_ids, n[None], slot, mesh, window=window, live=live)
            toks0 = sample_tokens(logits, keys[0], temps, top_ps, top_ks,
                                  None, seeds, lens)

            def body(carry, step_key):
                last, lens, ck, cv = carry
                logits, ck, cv = module.decode_step_paged(
                    params, cfg, last, lens, ck, cv, tables, mesh,
                    window=window, live=live)
                toks = sample_tokens(logits, step_key, temps, top_ps,
                                     top_ks, None, seeds, lens)
                return (toks, lens + 1, ck, cv), toks

            (last, lens, cache_k, cache_v), toks = jax.lax.scan(
                body, (toks0, lens + 1, cache_k, cache_v), keys[1:])
            toks = jnp.concatenate([first_in[None, :], toks0[None, :], toks],
                                   axis=0)
            return (last, lens, temps, top_ps, top_ks, seeds, cache_k,
                    cache_v, toks)

        return jax.jit(admit_many, donate_argnums=(3, 4))

    def verify(self, window: int, *, fused: bool,
               grammar: bool = False) -> Callable:
        """The verify step of this engine for a context-window bucket: the
        fused program (with or without the device grammar), or the legacy
        one (`LLMLB_FUSED_DECODE=0`, a grammar over its table budget)."""
        kind, k = "verify_fused" if fused else "verify", self.max_draft_tokens
        return self._program(
            kind, k, window, grammar, (kind, (k, window, grammar)),
            lambda: (self._build_verify_fused(window, grammar) if fused
                     else self._build_verify(window)))

    def _build_verify(self, window: int) -> Callable:
        """Jit one fused verify dispatch for a context-window bucket: the
        K+1-token extend (family verify step) plus per-position sampling —
        one device program, one host readback per verify step. Returns
        [B, K+2] tokens: column 0 echoes the input last-token column (the
        deferred-first-emission ride-along, same contract as decode's
        first_in row), columns 1.. are the model's samples per position."""
        module, cfg, mesh = self.module, self.cfg, self.mesh

        def run(params, ids, chunk_lens, start_pos, tables,
                cache_k, cache_v, temps, top_ps, top_ks, seeds, mask,
                key, lora_idx=None):
            logits, cache_k, cache_v, *_ = module.verify_step_paged(
                params, cfg, ids, chunk_lens, start_pos, tables,
                cache_k, cache_v, mesh, window=window,
                lora_idx=lora_idx,
            )
            toks = _sample_chunk(logits, key, temps, top_ps, top_ks,
                                 seeds, mask, start_pos)
            return (jnp.concatenate([ids[:, :1], toks], axis=1),
                    cache_k, cache_v)

        return jax.jit(run, donate_argnums=(5, 6))

    def _build_verify_fused(self, window: int, grammar: bool) -> Callable:
        """Jit the FUSED verify step: everything the legacy verify path did
        across several device programs — last-token splice into column 0,
        per-position grammar masks (device transition-table walk instead of
        the host FSM lookahead), the K+1-token extend, per-position
        sampling, accept counting, and the seq-len/last-token advance —
        compiled into ONE dispatch. Output tokens are [B, K+3]: column 0
        echoes the input last token, columns 1..K+1 the samples, and the
        final column the in-program accepted-prefix count per row."""
        module, cfg, mesh = self.module, self.cfg, self.mesh
        k1 = self.max_draft_tokens + 1

        def gram_mask(gram_table, gram_state, ids):
            # Column j's mask is the grammar state after consuming drafts
            # 1..j — the device analogue of the host pre-walk. A disallowed
            # draft clamps (grammar_advance), replicating the last live
            # state's row exactly like the legacy stripe padding; its
            # sample can then never equal the draft, so acceptance stops
            # at the same position the host truncation would have cut.
            s = gram_state
            biases = [grammar_bias(gram_table, s)]
            for j in range(1, k1):
                s = grammar_advance(gram_table, s, ids[:, j])
                biases.append(grammar_bias(gram_table, s))
            return jnp.stack(biases, axis=1).reshape(
                ids.shape[0] * k1, -1
            )

        def finish(ids, toks, chunk_lens, start_pos, lens, last_tokens,
                   active_mask):
            # accepted = longest prefix of drafts matching the model's own
            # samples — the same comparison the host emit loop walks
            # (tokens[i, 1+j] == d[j]), vectorized as a cumprod
            b = ids.shape[0]
            cols = jnp.arange(1, k1, dtype=jnp.int32)[None, :]
            matches = ((toks[:, :-1] == ids[:, 1:])
                       & (cols < chunk_lens[:, None]))
            accepted = jnp.sum(
                jnp.cumprod(matches.astype(jnp.int32), axis=1), axis=1
            ).astype(jnp.int32)
            # Active rows advance by accepted + 1 (the correction/bonus
            # sample); every other row — prefilling slots parked at
            # capacity-1, free slots — must keep its lens/last untouched,
            # which the host-side scatter got for free by only writing
            # surviving rows.
            new_lens = jnp.where(active_mask,
                                 start_pos + accepted + 1, lens)
            new_last = jnp.where(
                active_mask,
                toks[jnp.arange(b, dtype=jnp.int32), accepted],
                last_tokens,
            )
            out = jnp.concatenate(
                [ids[:, :1], toks, accepted[:, None]], axis=1
            )
            return out, new_last, new_lens

        def run(params, ids, chunk_lens, start_pos, tables,
                cache_k, cache_v, temps, top_ps, top_ks, seeds, key,
                last_tokens, active_mask, lens,
                gram_table=None, gram_state=None, lora_idx=None):
            ids = ids.at[:, 0].set(last_tokens)
            mask = (gram_mask(gram_table, gram_state, ids)
                    if grammar else None)
            logits, cache_k, cache_v, *_ = module.verify_step_paged(
                params, cfg, ids, chunk_lens, start_pos, tables,
                cache_k, cache_v, mesh, window=window,
                lora_idx=lora_idx,
            )
            toks = _sample_chunk(logits, key, temps, top_ps, top_ks,
                                 seeds, mask, start_pos)
            out, new_last, new_lens = finish(
                ids, toks, chunk_lens, start_pos, lens, last_tokens,
                active_mask,
            )
            return out, new_last, new_lens, cache_k, cache_v

        return jax.jit(run, donate_argnums=(5, 6))

    def block_many(self, window: int) -> Callable:
        """The burst of block passes of this engine (a block family's
        decode program) for a context-window bucket."""
        k = self.decode_burst
        return self._program(
            "block_many", k, window, False,
            ("block_many", (k, window, self.eos_id, self.slot_capacity)),
            lambda: self._build_block_many(k, window))

    def _build_block_many(self, k: int, window: int) -> Callable:
        """Jit a burst of k BLOCK PASSES (the decode program of a family
        that generates by diffusion over blocks; traced as `many`, like
        _build_decode_many's). Per row the scan carries the open block's ids
        and mask flags, the committed length, the positions left and the
        given tokens at the block's head. One pass is ONE call of the
        family's block pass (verify_step_paged) 2B positions wide. A row
        whose open block ENTERED the pass with no mask commits it, and where
        it goes on (no EOS past its given tokens in the block, positions
        left behind it) the commit rides with the next block's first
        unmasking: the row sends [its complete block | B masks], the layers
        write the block's K and V to the pool before the masks attend to
        them under the block mask, length += B, and the logits wanted are
        the second half's. Every other row sends [its open block | padding]
        (the padding goes to no expert and writes past the row's valid
        range) and wants the first half's. Then each wanted position's
        sampled id and its probability, and the masked positions of the
        open block unmask by the row's strategy: the `per_pass` most
        probable, and under `dynamic` every one above `threshold`. Rows are
        at different passes of their blocks. A row whose positions are used
        up, or whose committed block holds EOS past its given tokens, stops
        with that commit: it runs no further pass and writes to the slot's
        last cell alone, as the rows that are not decoding do. Returns the
        state, the caches, and ONE int32 array for the burst's one fetch:
        per pass B + 2 rows of [SLOTS] — the committed block's ids (-1: no
        commit), the positions unmasked (of the block behind a commit, in a
        pass that did both), whether the row ran — and the family's step
        counters behind them (_pack_step_counters)."""
        module, cfg, mesh = self.module, self.cfg, self.mesh
        shapes, max_names = self.counter_shapes, self._counter_max
        b, mask_id, eos = self.block, cfg.mask_token_id, self.eos_id
        park = self.slot_capacity - 1

        def many(params, blk, masked, lens, left, skip, cache_k, cache_v,
                 tables, temps, top_ps, top_ks, seeds, per_pass, dynamic,
                 threshold, key, live):
            keys = jax.random.split(key, k)
            offs = jnp.arange(b, dtype=jnp.int32)

            def body(carry, step_key):
                blk, masked, lens, left, skip, ck, cv = carry
                run = live & (left > 0)
                commit = run & ~jnp.any(masked, axis=1)
                stop = commit & ((left <= b) | jnp.any(
                    (blk == eos) & (offs[None, :] >= skip[:, None]), axis=1))
                fused = commit & ~stop  # the next block opens in this pass
                out_blk = jnp.where(commit[:, None], blk, -1)
                logits, ck, cv, *stats = module.verify_step_paged(
                    params, cfg,
                    jnp.concatenate([blk, jnp.full_like(blk, mask_id)], axis=1),
                    jnp.where(run, jnp.where(fused, 2 * b, b), 0),
                    jnp.where(run, lens, park), tables, ck, cv, mesh,
                    window=window, logits_from=jnp.where(fused, b, 0),
                    logits_len=b)
                # from here on the open block is the one the logits are of
                # (a row that stops opens none: nothing of it is masked)
                blk = jnp.where(fused[:, None], mask_id, blk)
                masked = masked | fused[:, None]
                lens = jnp.where(commit, lens + b, lens)
                ids, conf = _sample_block(
                    logits, step_key, temps, top_ps, top_ks, seeds, lens,
                    jnp.sum(masked, axis=1, dtype=jnp.int32))
                conf = jnp.where(masked, conf, -1.0)
                # rank among the block's masked positions, the more
                # probable first and of equals the earlier: [S, i, j] is
                # "j goes before i"
                ahead = (conf[:, None, :] > conf[:, :, None]) | (
                    (conf[:, None, :] == conf[:, :, None])
                    & (offs[None, None, :] < offs[None, :, None]))
                rank = jnp.sum(ahead, axis=2, dtype=jnp.int32)
                pick = ((rank < per_pass[:, None])
                        | (dynamic[:, None] & (conf > threshold[:, None])))
                pick = pick & masked & run[:, None]
                out = jnp.concatenate([
                    out_blk,
                    jnp.sum(pick, axis=1, dtype=jnp.int32)[:, None],
                    run[:, None].astype(jnp.int32)], axis=1)  # [S, B + 2]
                blk = jnp.where(pick, ids, blk)
                masked = masked & ~pick
                left = jnp.where(stop, 0, jnp.where(commit, left - b, left))
                skip = jnp.where(commit, 0, skip)
                return (blk, masked, lens, left, skip, ck, cv), (out.T, stats)

            (blk, masked, lens, left, skip, cache_k, cache_v), (
                out, stats) = jax.lax.scan(
                body, (blk, masked, lens, left, skip, cache_k, cache_v), keys)
            out = out.reshape(k * (b + 2), -1)
            if shapes:
                out = _pack_step_counters(out, stats[0], shapes, max_names)
            return blk, masked, lens, left, skip, cache_k, cache_v, out

        return jax.jit(many, donate_argnums=(6, 7))

    def prewarm_mixed(self, window: int, operands: tuple,
                      width: int) -> bool:
        """Lower and compile the mixed program (admit_many) of `window` off
        the loop's thread, so that the loop's one CALL of it at an empty
        house (EngineCore._build_mixed_program) and the first arrival to
        ride a burst of that window find it built (no traffic's warm-up
        forms a mixed burst: it sends into an empty house); whether it did.
        `operands`: what a dispatch hands the program before
        the live rows, as they stand NOW. What the loop DONATES — the pool,
        the per-slot state — goes in as a shape with the placement the
        loop's array has (no buffer of the loop's is touched from this
        thread; a page pool that no program has returned yet stands as
        fresh_kv_pool placed it, under another cache key than a returned
        one: the caller waits for the loop's first burst). What no program
        donates — the parameters, the block tables, the key — goes in
        itself: the tables ride a dispatch UNPLACED (jnp.asarray), and a
        placed shape in their stead lands under another key, which is why
        the lowering of `prewarm`, below, builds what no dispatch finds."""
        def placed(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                        sharding=x.sharding)

        params, *state, tables = operands[:6]
        *sampling, key = operands[6:]
        try:
            self.admit_many(window).lower(
                params, *jax.tree.map(placed, state), tables,
                *map(placed, sampling), key,
                np.zeros((self.num_slots,), np.bool_),
                np.zeros((1, width), np.int32), np.zeros((4,), np.int32),
                np.zeros((2,), np.float32)).compile()
        except Exception:  # pragma: no cover - best-effort warmup
            log.exception("window %d mixed prewarm failed (no arrival "
                          "rides a burst of that window)", window)
            return False
        return True

    def prewarm(self, windows: tuple[int, ...], operands: tuple, *,
                fused_decode: bool, running: Callable[[], bool]) -> None:
        """Compile every window-bucket variant of the decode program ahead
        of its first use. `operands`: what the scheduler hands that program
        at a dispatch, params first, without the live rows."""
        caches = (6, 7) if self.block > 1 else (3, 4)

        def shape(x, placed):
            # Shardings are part of jax's executable cache key: a prewarm
            # lowered without them compiles a different (unsharded) variant
            # and the real dispatch would still stall on a fresh compile.
            # Params and caches carry theirs. The per-slot vectors and the
            # key are lowered unspecified, while a dispatch hands them over
            # placed on the mesh: the two still land under different keys
            # (ROADMAP, Speed, "Set-up", cure (a)).
            return jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=x.sharding if placed else None)

        # the caches may be quantized {"q","s"} pytrees — map per leaf
        args = [jax.tree.map(partial(shape, placed=i == 0 or i in caches), x)
                for i, x in enumerate(operands)]
        live = jax.ShapeDtypeStruct((self.num_slots,), np.bool_)
        args.append(live)
        for w in windows:
            if not running():
                return
            try:
                if self.block > 1:
                    self.block_many(w).lower(*args).compile()
                elif self.decode_burst > 1 or fused_decode:
                    # fused engines dispatch the burst scan even at k == 1;
                    # grammar/fused-verify variants compile on first use
                    # (their tables don't exist until a schema registers)
                    self.decode_many(w).lower(*args).compile()
                else:
                    # single-step mode compiles decode_step_paged per window
                    params, last, lens, ck, cv, tables = args[:6]
                    self.module.decode_step_paged.lower(
                        params, self.cfg, last, lens, ck, cv, tables,
                        self.mesh, window=w, live=live,
                    ).compile()
            except Exception:  # pragma: no cover - best-effort warmup
                log.exception("window %d prewarm failed (will compile "
                              "on first use)", w)
