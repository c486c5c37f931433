"""Homa switch-arbitration Pallas TPU kernels — the simulator's per-slot hot
spots, TPU-ified (DESIGN.md §5): the "switch egress port" becomes a
vectorized arbitration kernel over the chunk buffer.

1. ``priority_arbiter``: per receiver row, select the buffered chunk to drain:
   strict priority, FIFO (insertion sequence) within a level. Lexicographic
   masked argmin over (prio, seq), tiled over buffer blocks with the running
   best carried in VMEM scratch.

2. ``srpt_topk``: per receiver row, the K messages with the best (largest)
   key — Homa's overcommitment grant set (top-K SRPT). Iterated masked max
   with running top-K value AND column registers in scratch, so the grant
   path gets the winning message ids directly (no re-matching scan).

Padding/block-size selection lives in ``dispatch.py``; these raw kernels
require exact block multiples.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BIG = 2 ** 30    # plain int: jnp constants would be captured as kernel operands
NEG = -(2 ** 30)  # ineligible key sentinel: below every legitimate key (>= 0)


# ------------------------------------------------- shared block math ------
# Both the staged kernels below and the fused kernel (fused.py) run these
# on one VMEM block. Every reduction keeps its row axis 2-D (``keepdims``)
# and every tie-break is a masked ``min`` over a column ``iota``: the TPU
# compiler lowers neither ``argmin`` on int32 nor ``cumsum`` nor the
# scatter behind ``.at[].set``.

def _first_col(hit, col):
    """Per row, the lowest ``col`` where ``hit`` holds, as ``(rows, 1)``
    (``BIG`` where nothing hits) — the first-occurrence rule of
    ``argmin`` and of ``lax.top_k``'s stable ties."""
    return jnp.min(jnp.where(hit, col, BIG), axis=1, keepdims=True)


def lex_argmin(prio, seq, elig):
    """Strict priority, then FIFO, over one ``(rows, cols)`` block.
    Returns ``(pmin, smin, idx)``, each ``(rows, 1)``: the winning
    priority and sequence (``BIG`` when the row has nothing eligible)
    and the lowest block column holding that pair (0 when nothing)."""
    p = jnp.where(elig, prio, BIG)
    s = jnp.where(elig, seq, BIG)
    pmin = jnp.min(p, axis=1, keepdims=True)
    s_cand = jnp.where(p == pmin, s, BIG)
    smin = jnp.min(s_cand, axis=1, keepdims=True)
    col = jax.lax.broadcasted_iota(jnp.int32, s_cand.shape, 1)
    return pmin, smin, _first_col(s_cand == smin, col)


def topk_rounds(cand_v, cand_i, K: int):
    """K rounds of masked max over candidate keys ``cand_v`` and their
    source columns ``cand_i`` (both ``(rows, n)``). Each round takes the
    FIRST occurrence of the row maximum, so ties resolve to the earliest
    candidate column. ``NEG`` is the neutral "taken/absent" key — NOT
    zero, which is a legitimate (ineligible) key value that must still
    outrank padding. Returns ``(vals, idx)``, each ``(rows, K)``."""
    rows = cand_v.shape[0]
    col = jax.lax.broadcasted_iota(jnp.int32, cand_v.shape, 1)
    rank = jax.lax.broadcasted_iota(jnp.int32, (rows, K), 1)
    tops_v = jnp.full((rows, K), NEG, jnp.int32)
    tops_i = jnp.full((rows, K), -1, jnp.int32)
    for r in range(K):
        m = jnp.max(cand_v, axis=1, keepdims=True)
        first = col == _first_col(cand_v == m, col)
        src = jnp.max(jnp.where(first, cand_i, -1), axis=1, keepdims=True)
        tops_v = jnp.where(rank == r, m, tops_v)
        tops_i = jnp.where(rank == r, src, tops_i)
        cand_v = jnp.where(first, NEG, cand_v)
        cand_i = jnp.where(first, -1, cand_i)
    return tops_v, tops_i


# ------------------------------------------------------ priority arbiter ---

def _arb_kernel(prio_ref, seq_ref, elig_ref, prio_out, idx_out,
                bp_scr, bs_scr, bi_scr, *, bc: int, ncap: int):
    # NB pallas binds (*ins, *outs, *scratch) — outputs before scratch
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        bp_scr[...] = jnp.full_like(bp_scr, BIG)
        bs_scr[...] = jnp.full_like(bs_scr, BIG)
        bi_scr[...] = jnp.zeros_like(bi_scr)

    pmin, smin, col = lex_argmin(prio_ref[...], seq_ref[...], elig_ref[...])
    col = col + ci * bc

    # merge with running best
    bp, bs = bp_scr[...], bs_scr[...]
    better = (pmin < bp) | ((pmin == bp) & (smin < bs))
    bp_scr[...] = jnp.where(better, pmin, bp)
    bs_scr[...] = jnp.where(better, smin, bs)
    bi_scr[...] = jnp.where(better, col, bi_scr[...])

    @pl.when(ci == ncap - 1)
    def _fin():
        prio_out[...] = bp_scr[...]
        idx_out[...] = bi_scr[...]


def priority_arbiter(prio, seq, elig, *, block_h: int = 8,
                     block_c: int = 256, interpret: bool = False):
    """prio/seq: (H, cap) int32; elig: (H, cap) bool.
    Returns (best_prio (H,), best_idx (H,)); best_prio == BIG if none."""
    H, cap = prio.shape
    bh = min(block_h, H)
    bc = min(block_c, cap)
    assert H % bh == 0 and cap % bc == 0
    ncap = cap // bc

    kernel = functools.partial(_arb_kernel, bc=bc, ncap=ncap)
    # per-row results are (H, 1) columns: the TPU refuses rank-1 blocks
    # narrower than 128 lanes
    bp, bi = pl.pallas_call(
        kernel,
        grid=(H // bh, ncap),
        in_specs=[pl.BlockSpec((bh, bc), lambda hi, ci: (hi, ci)),
                  pl.BlockSpec((bh, bc), lambda hi, ci: (hi, ci)),
                  pl.BlockSpec((bh, bc), lambda hi, ci: (hi, ci))],
        out_specs=[pl.BlockSpec((bh, 1), lambda hi, ci: (hi, 0)),
                   pl.BlockSpec((bh, 1), lambda hi, ci: (hi, 0))],
        out_shape=[jax.ShapeDtypeStruct((H, 1), jnp.int32),
                   jax.ShapeDtypeStruct((H, 1), jnp.int32)],
        # NB: distinct scratch objects — a repeated instance would alias
        scratch_shapes=[pltpu.VMEM((bh, 1), jnp.int32),
                        pltpu.VMEM((bh, 1), jnp.int32),
                        pltpu.VMEM((bh, 1), jnp.int32)],
        interpret=interpret,
        name="priority_arbiter",
    )(prio, seq, elig)
    return bp[:, 0], bi[:, 0]


# ---------------------------------------------------------- SRPT top-K -----

def _topk_kernel(key_ref, val_out, idx_out, val_scr, idx_scr, *,
                 K: int, bm: int, nm: int):
    mi = pl.program_id(1)

    @pl.when(mi == 0)
    def _init():
        val_scr[...] = jnp.full_like(val_scr, NEG)
        idx_scr[...] = jnp.full_like(idx_scr, -1)

    k = key_ref[...]                                        # (bh, bm) int32
    col = (jax.lax.broadcasted_iota(jnp.int32, k.shape, 1)
           + mi * bm)                                       # global columns
    # merge the block into the running top-K. The running tops sit before
    # the block columns and block columns ascend, so first-occurrence
    # extraction resolves ties to the lowest global column —
    # lax.top_k's stability.
    tops_v, tops_i = topk_rounds(
        jnp.concatenate([val_scr[...], k], axis=1),
        jnp.concatenate([idx_scr[...], col], axis=1), K)
    val_scr[...] = tops_v
    idx_scr[...] = tops_i

    @pl.when(mi == nm - 1)
    def _fin():
        val_out[...] = val_scr[...]
        idx_out[...] = idx_scr[...]


def srpt_topk(keys, K: int, *, block_h: int = 8, block_m: int = 512,
              interpret: bool = False):
    """keys: (H, M) int32, 0 = ineligible, larger = more urgent.
    Returns raw ``(vals (H, K), idx (H, K))`` int32: the K largest keys
    per row in descending order plus their source columns. Rows with
    fewer than K entries carry the ``NEG`` sentinel / -1 — callers
    normalize (``dispatch.pallas_topk`` clamps vals at 0 and masks idx)."""
    H, M = keys.shape
    bh = min(block_h, H)
    bm = min(block_m, M)
    assert H % bh == 0 and M % bm == 0
    nm = M // bm

    kernel = functools.partial(_topk_kernel, K=K, bm=bm, nm=nm)
    return pl.pallas_call(
        kernel,
        grid=(H // bh, nm),
        in_specs=[pl.BlockSpec((bh, bm), lambda hi, mi: (hi, mi))],
        out_specs=[pl.BlockSpec((bh, K), lambda hi, mi: (hi, 0)),
                   pl.BlockSpec((bh, K), lambda hi, mi: (hi, 0))],
        out_shape=[jax.ShapeDtypeStruct((H, K), jnp.int32),
                   jax.ShapeDtypeStruct((H, K), jnp.int32)],
        # NB: distinct scratch objects — a repeated instance would alias
        scratch_shapes=[pltpu.VMEM((bh, K), jnp.int32),
                        pltpu.VMEM((bh, K), jnp.int32)],
        interpret=interpret,
        name="srpt_topk",
    )(keys)
