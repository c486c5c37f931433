"""Fused per-slot arbitration mega-kernel (DESIGN.md §11).

One ``pallas_call`` per simulated slot covering all three arbitration
stages that ``dispatch.py`` previously issued as separate kernels:

  downlink drain   lexicographic (prio, seq) argmin over the receiver
                   rings — the math of ``kernel.priority_arbiter``
  uplink drain     the same argmin over the TOR uplink rings (leaf-spine
                   fabrics only)
  SRPT grant set   per-receiver top-K keys + source columns — the math
                   of ``kernel.srpt_topk``

The three stages are data-independent within a slot once hoisted to slot
start (the sim enforces the delay preconditions that make the hoist
bit-exact — see ``sim._fused_precompute`` and DESIGN.md §11), so the
kernel simply runs them back to back on whole-array VMEM blocks, which
removes two of the three HBM round-trips plus two kernel launches per
slot. Slots too large for VMEM (``dispatch.FUSED_VMEM_LIMIT_BYTES``) run
the staged kernels instead.

Each stage's math is the single-block execution of the corresponding
staged kernel — the same ``kernel.lex_argmin`` / ``kernel.topk_rounds``,
same first-occurrence tie breaks, same ``BIG``/``NEG`` sentinels — which
is why fused == staged is bit-exact and not merely close (the reductions
are reordered across *blocks*, never within a row).

Two entry points:

  ``fused_slot(...)``        single slot; inputs are pre-padded 2-D tiles
  ``fused_slot_batch(...)``  leading batch axis (one sweep-run per grid
                             program): ``grid=(B,)`` so a vmapped sweep
                             issues ONE kernel launch per slot for the
                             whole run batch instead of B

``fused_slot`` carries a ``jax.custom_batching.custom_vmap`` rule that
rewrites ``vmap(fused_slot)`` into ``fused_slot_batch`` — the chunked
sweep path (``repro.core.sweep``) gets the batched launch for free, with
unbatched operands broadcast. Padding/shape policy lives in
``dispatch.fused_slot``; these entry points require exact tile multiples.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.arbiter.kernel import NEG, lex_argmin, topk_rounds

# scoped-VMEM limit of both kernel forms, whose operands and temporaries
# all live in VMEM at once (a v5e has 128 MiB; the compiler's default
# scope is far smaller). dispatch.FUSED_VMEM_LIMIT_BYTES is the largest
# slot that compiles within it.
VMEM_BUDGET_BYTES = 100 * 2 ** 20


# ------------------------------------------------------------ the kernel ---

def _fused_kernel(*refs, K: int, has_down: bool, has_up: bool,
                  has_topk: bool):
    """(*ins, *outs) refs in stage order, each one run's whole arrays
    (the batched form squeezes its run axis out of every block)."""
    n_in = 3 * has_down + 3 * has_up + has_topk
    ins, outs = refs[:n_in], refs[n_in:]
    i = o = 0
    for present in (has_down, has_up):
        if present:
            pmin, _, idx = lex_argmin(ins[i][...], ins[i + 1][...],
                                      ins[i + 2][...])
            outs[o][...] = pmin
            outs[o + 1][...] = idx
            i += 3
            o += 2
    if has_topk:
        keys = ins[i][...]
        Hb = keys.shape[0]
        # the staged kernel's first block, with empty running tops in
        # front: same candidates, same tie-breaks
        col = jax.lax.broadcasted_iota(jnp.int32, keys.shape, 1)
        tv, ti = topk_rounds(
            jnp.concatenate([jnp.full((Hb, K), NEG, jnp.int32), keys],
                            axis=1),
            jnp.concatenate([jnp.full((Hb, K), -1, jnp.int32), col],
                            axis=1), K)
        outs[o][...] = tv
        outs[o + 1][...] = ti


def _out_shapes(arrays, K: int, has_down: bool, has_up: bool,
                has_topk: bool):
    """Kernel output shapes (one run) in stage order. Per-row drain
    results are ``(rows, 1)`` columns: the TPU tiles the last two block
    dims, so a rank-1 ``(rows,)`` output cannot take a batched block."""
    shapes = []
    i = 0
    if has_down:
        H = arrays[i].shape[-2]
        shapes += [(H, 1), (H, 1)]
        i += 3
    if has_up:
        U = arrays[i].shape[-2]
        shapes += [(U, 1), (U, 1)]
        i += 3
    if has_topk:
        H2 = arrays[i].shape[-2]
        shapes += [(H2, K), (H2, K)]
    return shapes


def _squeeze_rows(outs, has_topk: bool):
    """Kernel outputs -> the raw convention: drain results ``(..., rows)``,
    top-K results ``(..., rows, K)``."""
    n_drain = len(outs) - 2 * has_topk
    return tuple(o[..., 0] if j < n_drain else o
                 for j, o in enumerate(outs))


def _call_single(arrays, K, has_down, has_up, has_topk, interpret):
    kernel = functools.partial(_fused_kernel, K=K, has_down=has_down,
                               has_up=has_up, has_topk=has_topk)
    out_shape = [jax.ShapeDtypeStruct(s, jnp.int32)
                 for s in _out_shapes(arrays, K, has_down, has_up,
                                      has_topk)]
    # no grid: one program, whole-array VMEM refs — dispatch.fused_slot
    # guarantees the operands fit (falls back to staged kernels otherwise)
    return pl.pallas_call(
        kernel, out_shape=out_shape, interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_BUDGET_BYTES),
        name="fused_slot")(*arrays)


def _call_batch(arrays, K, has_down, has_up, has_topk, interpret):
    B = arrays[0].shape[0]
    kernel = functools.partial(_fused_kernel, K=K, has_down=has_down,
                               has_up=has_up, has_topk=has_topk)

    def spec(shape):
        # run axis squeezed out: the kernel sees one run's whole arrays
        return pl.BlockSpec((None,) + shape,
                            lambda b, nd=len(shape): (b,) + (0,) * nd)

    shapes = _out_shapes(arrays, K, has_down, has_up, has_topk)
    return pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=[spec(a.shape[1:]) for a in arrays],
        out_specs=[spec(s) for s in shapes],
        out_shape=[jax.ShapeDtypeStruct((B,) + s, jnp.int32)
                   for s in shapes],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_BUDGET_BYTES),
        name="fused_slot_batch",
    )(*arrays)


@functools.lru_cache(maxsize=None)
def _fused_fn(K: int, has_down: bool, has_up: bool, has_topk: bool,
              interpret: bool):
    """Cached custom-vmap callable for one static stage structure.
    Calling it plain runs the single-slot kernel; under ``vmap`` (the
    sweep paths) the rule below swaps in the ``grid=(B,)`` batched
    variant — one launch per slot for the whole run batch."""

    @jax.custom_batching.custom_vmap
    def fn(*arrays):
        return _squeeze_rows(_call_single(arrays, K, has_down, has_up,
                                          has_topk, interpret), has_topk)

    @fn.def_vmap
    def _rule(axis_size, in_batched, *arrays):  # noqa: ANN001
        arrays = tuple(
            a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
            for a, b in zip(arrays, in_batched))
        outs = _squeeze_rows(_call_batch(arrays, K, has_down, has_up,
                                         has_topk, interpret), has_topk)
        return outs, tuple(True for _ in outs)

    return fn


# ---------------------------------------------------------- entry points ---

def fused_slot(down=None, up=None, keys=None, K: int = 0, *,
               interpret: bool = False):
    """One fused arbitration slot. All operands pre-padded to exact TPU
    tile multiples (rows→8, cols→128 — ``dispatch.pad_tiles``):

      down/up  ``(prio, seq, elig)`` with ``BIG``/``BIG``/``False`` pads
      keys     ``(H, M)`` int32 top-K keys, ``NEG``-padded, with ``K`` ≥ 1

    Returns raw per-stage outputs in stage order:
    ``[d_prio, d_idx][, u_prio, u_idx][, vals, idx]`` — the same raw
    convention as ``kernel.priority_arbiter`` / ``kernel.srpt_topk``
    (callers normalize). Under ``vmap`` this dispatches the batched
    ``grid=(B,)`` variant via ``custom_vmap``."""
    arrays = []
    if down is not None:
        arrays += list(down)
    if up is not None:
        arrays += list(up)
    if keys is not None:
        arrays.append(keys)
    fn = _fused_fn(K, down is not None, up is not None, keys is not None,
                   interpret)
    return fn(*arrays)


def fused_slot_batch(down=None, up=None, keys=None, K: int = 0, *,
                     interpret: bool = False):
    """Explicit batched variant: every operand carries a leading batch
    axis and the kernel runs with ``grid=(B,)`` — one program per batch
    element, one launch total. Same raw output convention as
    :func:`fused_slot` with the batch axis prepended."""
    arrays = []
    if down is not None:
        arrays += list(down)
    if up is not None:
        arrays += list(up)
    if keys is not None:
        arrays.append(keys)
    return _squeeze_rows(_call_batch(tuple(arrays), K, down is not None,
                                     up is not None, keys is not None,
                                     interpret), keys is not None)


__all__ = ["fused_slot", "fused_slot_batch"]
