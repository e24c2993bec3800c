"""The program's routing, for the comparison behind `correct` of a mixture
(benchmark/correctness.py, benchmark/reference/moe.py).

`observed(family, name)` is the family's paged serving function `name`
giving one more value at the end: per layer and token of the call, the
experts chosen `[L, B, T, k]`, the router's float32 logits `[L, B, T, X]`,
and which (token, choice) pairs were kept `[L, B, T, k]` (all true for a
program that drops nothing).

The contract with the program is a static argument `routing=True` on those
functions that makes them return exactly that; a family that has it is
asked, and what it reports is what the comparison counts. A family that has
not is tapped: `_Tap` reads the same three arrays from the outside. It
traces the function's own body again under a `jit` of its own, with
`ops.moe.top_k_routing` wrapped so that each call sends its inputs and its
choice to the host (`jax.debug.callback`, ordered, so layer by layer). What
the tap requires of the program is that alone: `top_k_routing(router_logits
[S, X], k)` called once per layer of a traced call. Where `kept` comes
from: while the program has a capacity dispatch (a `moe_dispatch_combine`
in `ops/moe.py`, found with `getattr`, wrapped to note the `capacity` and
`token_valid` it is called with), the tap works out which pairs it keeps
from the choice by GShard's rule as `ops/moe.py` states it (choice-major,
then by token, up to `capacity` per expert); a call that goes through no
such dispatch, or a program that has none, keeps every pair. The program's
own jitted functions are never traced with the wrapper in place: the
engine's programs are the ones they were.

`_Tap`, `_wrapped_routing` and `kept_by_capacity` are for the `benchmark`
PR after the one that gives `models/mixtral.py` the `routing=` argument to
delete (PERF.md section 7): from then on no family is tapped.
"""

from __future__ import annotations

import contextlib
import functools
import inspect

import jax
import jax.numpy as jnp
import numpy as np


@functools.cache  # a tap traces and compiles once per shape, like a jit
def observed(family, name: str):
    fn = getattr(family, name)
    if "routing" in inspect.signature(fn).parameters:
        return functools.partial(fn, routing=True)
    return _Tap(family, fn)


def kept_by_capacity(chosen, valid, capacity: int | None, experts: int):
    """chosen [S, k], valid [S] -> kept [S, k]: each expert takes its first
    `capacity` assignments, first choices of all tokens before second
    choices, tokens in order; padding takes no room and counts as kept."""
    s, k = chosen.shape
    if capacity is None:
        return np.ones((s, k), bool)
    one_hot = (chosen[..., None] == np.arange(experts)) & valid[:, None, None]
    flat = one_hot.transpose(1, 0, 2).reshape(k * s, experts).astype(np.int64)
    before = (np.cumsum(flat, axis=0) - flat).reshape(k, s, experts)
    kept = ((before.transpose(1, 0, 2) < capacity) & one_hot).any(axis=-1)
    return kept | ~valid[:, None]


@contextlib.contextmanager
def _wrapped_routing(family, records: list):
    from llmlb_tpu.ops import moe

    real_top_k = moe.top_k_routing
    # a program that drops nothing may have no capacity dispatch at all
    real_dispatch = getattr(moe, "moe_dispatch_combine", None)
    dispatching: dict = {}  # capacity and token_valid of the call being traced

    def top_k_routing(router_logits, num_selected):
        weights, chosen = real_top_k(router_logits, num_selected)
        capacity = dispatching.get("capacity")
        valid = dispatching.get("token_valid")
        if valid is None:
            valid = jnp.ones(chosen.shape[:1], bool)

        def record(logits, chosen, valid):
            chosen, valid = np.asarray(chosen), np.asarray(valid)
            records.append((chosen, np.asarray(logits, np.float32),
                            kept_by_capacity(chosen, valid, capacity,
                                             logits.shape[-1])))

        jax.debug.callback(record, router_logits, chosen, valid, ordered=True)
        return weights, chosen

    def moe_dispatch_combine(*args, capacity, token_valid=None, **kw):
        dispatching.update(capacity=capacity, token_valid=token_valid)
        try:
            return real_dispatch(*args, capacity=capacity,
                                 token_valid=token_valid, **kw)
        finally:
            dispatching.clear()

    holders = [m for m in (moe, family) if real_dispatch is not None
               and getattr(m, "moe_dispatch_combine", None) is real_dispatch]
    moe.top_k_routing = top_k_routing
    for m in holders:
        m.moe_dispatch_combine = moe_dispatch_combine
    try:
        yield
    finally:
        moe.top_k_routing = real_top_k
        for m in holders:
            m.moe_dispatch_combine = real_dispatch


class _Tap:
    def __init__(self, family, fn):
        body = fn.__wrapped__  # under the program's jax.jit
        names = inspect.signature(body).parameters

        @functools.wraps(body)
        def traced_apart(*args, **kw):  # another function than the program
            return body(*args, **kw)    # jits, so another trace cache

        self._family = family
        self._records: list = []
        self._jit = jax.jit(
            traced_apart,
            static_argnames=[n for n in ("cfg", "mesh", "window") if n in names],
            donate_argnames=("cache_k", "cache_v"))

    def __call__(self, params, cfg, input_ids, *args, **kw):
        self._records.clear()
        with _wrapped_routing(self._family, self._records):
            out = self._jit(params, cfg, input_ids, *args, **kw)
        jax.effects_barrier()
        if len(self._records) != cfg.num_layers:
            raise RuntimeError(
                f"{self._jit.__name__}: {len(self._records)} routing "
                f"decisions were heard for {cfg.num_layers} layers")
        b = input_ids.shape[0]  # the family flattens [B, T] to [S]
        return (*out, tuple(
            (layers := np.stack(per_layer)).reshape(
                cfg.num_layers, b, -1, layers.shape[-1])
            for per_layer in zip(*self._records)))
