"""Pure-jnp oracles for the arbitration kernels (the same math the simulator
uses inline)."""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

BIG = jnp.int32(2 ** 30)


def priority_arbiter_ref(prio, seq, elig):
    """Strict-priority, FIFO-within-level selection per row.
    Returns (best_prio (H,), best_idx (H,))."""
    p = jnp.where(elig, prio, BIG)
    s = jnp.where(elig, seq, BIG)
    pmin = p.min(axis=1)
    s_cand = jnp.where(p == pmin[:, None], s, BIG)
    idx = jnp.argmin(s_cand, axis=1).astype(jnp.int32)
    return pmin, idx


NEG = jnp.int32(-(2 ** 30))   # ineligible key sentinel (see kernel.py)


def srpt_topk_ref(keys, K: int):
    """K largest keys per row plus their source columns.
    Returns ``(vals (H, K), idx (H, K))``: descending keys clamped at 0,
    columns -1 where fewer than K positive keys exist. Short rows pad
    with the ``NEG`` sentinel — not zero, which is a legitimate
    (ineligible) key value that must still outrank padding."""
    if keys.shape[1] < K:
        keys = jnp.pad(keys, ((0, 0), (0, K - keys.shape[1])),
                       constant_values=NEG)
    vals, idx = lax.top_k(keys, K)
    return (jnp.maximum(vals, 0).astype(jnp.int32),
            jnp.where(vals > 0, idx.astype(jnp.int32), -1))


def srpt_topk_rounds(keys, K: int):
    """:func:`srpt_topk_ref` without the sort: K unrolled selection
    rounds, bit-identical to it for every input. Round r takes each
    row's greatest (key, then lowest column) among the entries strictly
    after round r-1's winner ``(v, c)`` — ``key < v``, or ``key == v``
    and ``col > c`` — starting from ``(INT32_MAX, -1)``. Only the
    ``(H, 1)`` winner is carried between rounds, so a round reads the
    keys once and writes nothing back to the ``(H, M)`` matrix."""
    if keys.shape[1] < K:
        keys = jnp.pad(keys, ((0, 0), (0, K - keys.shape[1])),
                       constant_values=NEG)
    H, M = keys.shape
    lo, hi = jnp.iinfo(jnp.int32).min, jnp.iinfo(jnp.int32).max
    col = lax.broadcasted_iota(jnp.int32, (H, M), 1)
    v = jnp.full((H, 1), hi, jnp.int32)
    c = jnp.full((H, 1), -1, jnp.int32)
    vals, idx = [], []
    for _ in range(K):
        after = (keys < v) | ((keys == v) & (col > c))
        v = jnp.max(jnp.where(after, keys, lo), axis=1, keepdims=True)
        c = jnp.min(jnp.where(after & (keys == v), col, M), axis=1,
                    keepdims=True)
        vals.append(v)
        idx.append(c)
    vals = jnp.concatenate(vals, axis=1)
    idx = jnp.concatenate(idx, axis=1)
    return jnp.maximum(vals, 0), jnp.where(vals > 0, idx, -1)
