"""Backend dispatch for the per-slot arbitration hot path (DESIGN.md §6).

One entry point per arbitration primitive, each routable to either
compute backend:

  ``arbitrate(prio, seq, elig, backend=...)``   strict-priority-then-FIFO
      winner per row — the math of ``fabric.ring_drain_select``.
  ``topk(keys, K, backend=...)``                per-row top-K (values AND
      source columns) — the receiver's SRPT grant-set selection.
  ``fused_slot(down=..., up=..., topk=...)``    all of a slot's stages in
      ONE kernel launch — the ``pallas_fused`` backend's entry point
      (DESIGN.md §11), called from ``sim._fused_precompute``.

``backend="reference"`` runs the pure-jnp oracles (``ref.py``);
``backend="pallas"`` runs the Pallas TPU kernels (``kernel.py``) through
the padded wrappers below; ``backend="pallas_fused"`` additionally fuses
the three per-slot stages into one launch (``fused.py``) — the staged
primitives below still serve its non-fusable call sites. All backends
are bit-identical by contract — the golden-snapshot tests in
``tests/test_backend.py``, the differential fuzz harness in
``tests/test_differential.py``, and the property tests in
``tests/test_kernels.py`` enforce it — so ``SimConfig.backend`` is a
pure performance knob.

This module also owns the padding/block-size heuristics, shared by every
wrapper through :func:`pad_tiles`: rows pad to the 8-sublane multiple,
columns pad to the 128-lane multiple (the TPU tile for int32), and the
block size is the largest preferred power of two dividing the padded
dimension. Padding values are chosen so padded entries can never win
(``BIG`` priority / ``False`` eligibility / the ``NEG`` key sentinel —
NOT zero, which is a legitimate key value).

Interpret-mode selection (``resolve_interpret``): on a TPU the kernels
compile (``tests/test_tpu_compile.py`` holds them to the v5e compiler);
off-TPU the pallas backends auto-select ``interpret=True``, which traces
each kernel into plain XLA ops so the CPU tests can run it. Interpret
mode is for those tests only. ``SIM_PALLAS_INTERPRET=0|1`` overrides.
"""
from __future__ import annotations

import math
import os
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.arbiter.kernel import (priority_arbiter, srpt_topk,
                                          BIG, NEG)
from repro.kernels.arbiter.ref import (priority_arbiter_ref, srpt_topk_ref,
                                      srpt_topk_rounds)
from repro.kernels.arbiter import fused as fused_mod

BACKENDS = ("reference", "pallas", "pallas_fused")
_ROW_UNIT = 8          # TPU sublane multiple for int32 blocks
_COL_UNIT = 128        # TPU lane multiple

# operand bytes (fused_operand_bytes) above which fused_slot runs the
# staged per-stage kernels instead — still pallas, still bit-identical.
# The fused kernel holds whole arrays in VMEM. At fused.VMEM_BUDGET_BYTES
# the v5e compiler took a paper-width slot of 45 MiB in both the single
# and the batched form and refused the batched one at 58.5 MiB;
# tests/test_tpu_compile.py compiles a slot of this size in both forms.
FUSED_VMEM_LIMIT_BYTES = 32 * 2 ** 20

# The reference top-K of (H, M) keys either runs K read-only selection
# rounds (ref.srpt_topk_rounds) or lax.top_k, which XLA lowers to a full
# sort of every row whatever K (ref.srpt_topk_ref). topk_rounds picks
# from the static K and M. One jitted top-K of a (144, M) int32 matrix
# built as srpt_grant_matrix builds it, us per call on one TPU v5e
# (a loop of 200 calls in one jit; lax.top_k's time does not move with K):
#
#      M  lax.top_k  rounds, K: us
#    128        7.7  4: 8.4   6: 10.4   8: 12.1
#    256       16.9  7: 11.1  13: 16.8  16: 19.3
#    512       29.5  1: 5.8   7: 11.2   16: 19.8  32: 34.1  64: 63.3
#   1024       56.5  32: 39.3  40: 47.8  48: 55.2
#   2048      114.6  48: 84.5  59: 101.2  64: 109.4  81: 136.5
#   6000        679  1: 12.5  7: 37.5  16: 77.5  32: 142.5  128: 539.4
#   8192        588  64: 353.2  92: 501.9  104: 566.6  128: 693.3
#
# A round costs about 1 us up to M = 1024, 1.6 us at 2048 and 4-5 us at
# 6000-8192; the sort grows as M log2(M) (5-9 ns x M log2(M), most at M = 6000). The
# crossovers lie near K = 3, 13, 27, 49, 67, 160 and 108 at the M above.
# The rule K (M + 2000) <= 9 M log2(M) stays under every one of them:
# rounds for K <= 3, 8, 16, 30, 50, 84 and 94.


def resolve_backend(name: str | None) -> str:
    """``None`` -> ``$SIM_BACKEND`` (default ``reference``); unknown
    names raise a ``ValueError`` listing the choices."""
    if name is None:
        name = os.environ.get("SIM_BACKEND") or "reference"
    if name not in BACKENDS:
        raise ValueError(f"unknown backend {name!r}; expected one of "
                         f"{list(BACKENDS)} (or $SIM_BACKEND)")
    return name


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` -> auto: interpret everywhere except on a real TPU,
    overridable via ``$SIM_PALLAS_INTERPRET``."""
    if interpret is not None:
        return interpret
    # empty string == unset, the same convention resolve_backend uses
    env = os.environ.get("SIM_PALLAS_INTERPRET")
    if env:
        return env.lower() not in ("0", "false")
    return jax.default_backend() != "tpu"


# ------------------------------------------------- padding heuristics ------

def _padded_dim(n: int, unit: int) -> int:
    return -(-n // unit) * unit


def _block(n_padded: int, preferred: int, unit: int) -> int:
    """Largest power-of-two multiple of ``unit`` that divides the padded
    dimension, capped at ``preferred`` (a power-of-two multiple of
    ``unit``). Never degenerates to one un-tiled block."""
    b = preferred
    while b > unit and n_padded % b:
        b //= 2
    return min(b, n_padded)


def _pad2(x, rows: int, cols: int, fill):
    """Pad a 2-D array up to (rows, cols) with ``fill``."""
    H, C = x.shape
    if rows == H and cols == C:
        return x
    return jnp.pad(x, ((0, rows - H), (0, cols - C)), constant_values=fill)


def pad_tiles(arrs, fills, *, col_pref: int = 256):
    """THE shared pad-and-tile policy (used by ``arbitrate``, ``topk``
    and the fused entry point): pad each same-shape 2-D array in ``arrs``
    to the TPU tile — rows to the 8-sublane multiple, columns to the
    128-lane multiple — with its own can't-win ``fill``, and pick block
    sizes (rows block 8; columns the largest power-of-two multiple of
    128 dividing the padded width, capped at ``col_pref``).

    Returns ``(padded_arrays, (block_h, block_c))``."""
    H, C = arrs[0].shape
    Hp = _padded_dim(H, _ROW_UNIT)
    Cp = _padded_dim(C, _COL_UNIT)
    bh = _block(Hp, _ROW_UNIT, _ROW_UNIT)
    bc = _block(Cp, col_pref, _COL_UNIT)
    return tuple(_pad2(a, Hp, Cp, f)
                 for a, f in zip(arrs, fills)), (bh, bc)


def pad_min_cols(keys, K: int):
    """Top-K inputs narrower than K widen to K columns with the ``NEG``
    sentinel — never zero: 0 is a legitimate (ineligible) key value and
    must still outrank padding so indices stay in-bounds."""
    H, M = keys.shape
    if M < K:
        keys = jnp.pad(keys, ((0, 0), (0, K - M)), constant_values=NEG)
    return keys


# ---------------------------------------------------- pallas wrappers ------

@partial(jax.jit, static_argnames=("interpret",))
def pallas_arbitrate(prio, seq, elig, *, interpret: bool = False):
    """Padded ``priority_arbiter`` call: returns ``(best_prio, best_idx)``
    per row, ``best_prio == BIG`` (and ``best_idx == 0``) if the row has
    no eligible entry — exactly ``ref.priority_arbiter_ref``."""
    H = prio.shape[0]
    (pp, sp, ep), (bh, bc) = pad_tiles((prio, seq, elig),
                                       (BIG, BIG, False), col_pref=256)
    bp, bi = priority_arbiter(pp, sp, ep, block_h=bh, block_c=bc,
                              interpret=interpret)
    return bp[:H], bi[:H]


def _topk_normalize(vals, idx):
    """Raw kernel top-K -> caller convention: descending keys clamped at
    0, columns -1 where fewer than K positive keys exist."""
    return jnp.maximum(vals, 0), jnp.where(vals > 0, idx, -1)


@partial(jax.jit, static_argnames=("K", "interpret"))
def pallas_topk(keys, K: int, *, interpret: bool = False):
    """Padded ``srpt_topk`` call: returns ``(vals, idx)`` — the K largest
    keys per row (descending, clamped at 0) and their source columns
    (-1 where fewer than K positive keys exist)."""
    H = keys.shape[0]
    keys = pad_min_cols(keys, K)
    (kp,), (bh, bm) = pad_tiles((keys,), (NEG,), col_pref=512)
    vals, idx = srpt_topk(kp, K, block_h=bh, block_m=bm,
                          interpret=interpret)
    return _topk_normalize(vals[:H], idx[:H])


def fused_operand_bytes(down=None, up=None, keys=None, K: int = 0) -> int:
    """What :func:`fused_slot` weighs against ``FUSED_VMEM_LIMIT_BYTES``,
    from the padded shapes: three 4-byte ``(rows, cols)`` arrays per drain
    stage, the ``(H2, M)`` keys and the two ``(H2, K)`` top-K outputs."""
    nbytes = 0
    for shape in (down, up):
        if shape is not None:
            nbytes += 12 * shape[0] * shape[1]
    if keys is not None:
        nbytes += 4 * keys[0] * keys[1] + 8 * keys[0] * K
    return nbytes


def fused_slot(down=None, up=None, topk=None, *,
               interpret: bool | None = None):
    """The ``pallas_fused`` backend's per-slot entry point: pad every
    present stage with the shared :func:`pad_tiles` policy and issue ONE
    ``fused.fused_slot`` kernel launch (DESIGN.md §11).

      down / up   ``(prio (H, cap), seq, elig)`` — downlink / TOR-uplink
                  drain problems (either may be ``None``)
      topk        ``(keys (H2, M), K)`` — the SRPT grant-set problem

    Returns a dict with a key per present stage: ``"down"``/``"up"`` ->
    ``(best_prio (H,), best_idx (H,))`` exactly as :func:`arbitrate`;
    ``"topk"`` -> normalized ``(vals (H2, K), idx (H2, K))`` exactly as
    :func:`topk`. Operands too large for whole-array VMEM blocks
    (``FUSED_VMEM_LIMIT_BYTES``) fall back to the staged per-stage
    kernels — bit-identical either way."""
    interpret = resolve_interpret(interpret)
    d_pad = u_pad = k_pad = None
    K = 0
    if down is not None:
        d_pad, _ = pad_tiles(down, (BIG, BIG, False))
    if up is not None:
        u_pad, _ = pad_tiles(up, (BIG, BIG, False))
    if topk is not None:
        keys, K = topk
        (k_pad,), _ = pad_tiles((pad_min_cols(keys, K),), (NEG,))
    nbytes = fused_operand_bytes(
        down=None if d_pad is None else d_pad[0].shape,
        up=None if u_pad is None else u_pad[0].shape,
        keys=None if k_pad is None else k_pad.shape, K=K)
    if nbytes > FUSED_VMEM_LIMIT_BYTES:
        out = {}
        if down is not None:
            out["down"] = pallas_arbitrate(*down, interpret=interpret)
        if up is not None:
            out["up"] = pallas_arbitrate(*up, interpret=interpret)
        if topk is not None:
            out["topk"] = pallas_topk(topk[0], topk[1],
                                      interpret=interpret)
        return out
    raw = fused_mod.fused_slot(down=d_pad, up=u_pad, keys=k_pad, K=K,
                               interpret=interpret)
    raw = list(raw)
    out = {}
    if down is not None:
        H = down[0].shape[0]
        out["down"] = (raw[0][:H], raw[1][:H])
        raw = raw[2:]
    if up is not None:
        U = up[0].shape[0]
        out["up"] = (raw[0][:U], raw[1][:U])
        raw = raw[2:]
    if topk is not None:
        H2 = topk[0].shape[0]
        out["topk"] = _topk_normalize(raw[0][:H2], raw[1][:H2])
    return out


# -------------------------------------------------------- dispatchers ------

def arbitrate(prio, seq, elig, *, backend: str = "reference",
              interpret: bool | None = None):
    """Strict-priority, FIFO-within-level winner per row on the chosen
    backend. Returns ``(best_prio (H,), best_idx (H,))``; rows with no
    eligible entry return ``(BIG, 0)``. Bit-identical across backends.
    ``pallas_fused`` routes here for call sites outside the fused slot
    (they run the staged kernel)."""
    if resolve_backend(backend) == "reference":
        return priority_arbiter_ref(prio, seq, elig)
    return pallas_arbitrate(prio, seq, elig,
                            interpret=resolve_interpret(interpret))


def topk_rounds(K: int, M: int, backend: str = "reference") -> int:
    """Selection rounds that :func:`topk` of ``(H, M)`` keys runs on
    ``backend``: K where it selects by rounds (the pallas kernels always,
    the reference path up to the crossover measured above), 0 where it
    sorts. The reference path of :func:`topk` takes its branch from this
    function, so a count read from it cannot disagree with the program."""
    if K < 1:
        return 0
    if resolve_backend(backend) != "reference":
        return K
    return K if M > 1 and K * (M + 2000) <= 9 * M * math.log2(M) else 0


def topk(keys, K: int, *, backend: str = "reference",
         interpret: bool | None = None):
    """Per-row top-K keys + source columns on the chosen backend.
    Returns ``(vals (H, K), idx (H, K))``: descending keys clamped at 0,
    columns -1 where fewer than K positive keys exist. Ties resolve to
    the lowest column on every backend (``lax.top_k`` stability).

    The reference backend has two forms, bit-identical to each other:
    K read-only selection rounds (``ref.srpt_topk_rounds``), whose cost
    grows as K x H x M, and ``lax.top_k`` (``ref.srpt_topk_ref``, the
    test oracle), a full sort of every row whatever K. The static K
    picks between them (:func:`topk_rounds`)."""
    if resolve_backend(backend) == "reference":
        if topk_rounds(K, keys.shape[1]):
            return srpt_topk_rounds(keys, K)
        return srpt_topk_ref(keys, K)
    return pallas_topk(keys, K, interpret=resolve_interpret(interpret))


__all__ = ["BACKENDS", "resolve_backend", "resolve_interpret",
           "arbitrate", "topk", "topk_rounds", "fused_slot", "pad_tiles",
           "pad_min_cols", "pallas_arbitrate", "pallas_topk",
           "FUSED_VMEM_LIMIT_BYTES", "fused_operand_bytes"]
