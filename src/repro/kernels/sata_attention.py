"""SATA block-sparse flash attention — Pallas TPU kernel.

TPU-native embodiment of the paper's insight: SATA's key sorting
concentrates each query's selected keys into contiguous runs, so after
permuting K/V by ``kv_order`` and grouping queries by HEAD/GLOB/TAIL
class, whole (q_block × k_block) tiles of the score matrix are empty.
The kernel walks the (bh, q_block, k_block) grid with flash-style online
softmax and **skips all compute for empty tiles** (``@pl.when`` on the
prefetched block map) — the MXU analogue of gating whole CIM sub-array
passes, at the granularity the MXU actually exploits (128×128 tiles).

Three execution modes:
  * block mode     (no selection operand) — dense math inside occupied
    tiles, exactly the paper's energy model ("MACs are dense, albeit in
    a subset of tiles").  A ``causal=True`` request is still honored:
    the compacted grid gates future keys with the position operands.
  * exact mode     (``mask`` given) — additionally applies the element-
    level top-k mask inside each tile; bit-exact selective attention.
    The mask is a (BH, Sq, Sk) resident — the quadratic operand the
    threshold mode exists to avoid.
  * threshold mode (``thresholds`` given; compacted grid only) — the
    element mask is *re-derived per tile* from a (BH, Sq, 1) per-row
    top-k threshold: ``bf16(score) >= bf16(thr)``, the exact compare the
    bisect selection (``models.attention.kth_largest_bisect``) counted
    with, AND-ed with causality from ``q_pos``/``k_pos`` operands that
    ride through the same prefetched index maps as K/V (so they survive
    any key permutation).  Selection state entering the kernel is O(S):
    this is the chunked selection pipeline's back end — pass 1 streams
    ``q_chunk × Sk`` score tiles to bisect per-row thresholds, pass 2
    reduces the same tiles to the block occupancy map
    (``core.blockmap.compact_plan_from_chunks``), and no (BH, Sq, Sk)
    score tensor or boolean mask ever exists.

Scheduling: dense grid vs compacted grid
----------------------------------------
``sata_block_attention`` (dense grid) walks the full
``(BH, n_q_blocks, n_k_blocks)`` grid and gates *compute* on the
prefetched block map — but the BlockSpec pipeline still streams every
K/V tile through VMEM, so HBM traffic stays quadratic and wall-clock
barely tracks the block-skip fraction.  It is kept as the baseline the
benchmarks measure against.

``sata_block_attention_compact`` is the SATA scheduler proper: the
planner (``core.blockmap.compact_kv_plan``) compresses each
``(bh, q_block)`` row of the occupancy map into an ascending list of
occupied k-block indices (``kv_indices (BH, nqb, P)``) plus a count
(``kv_counts (BH, nqb)``).  Both ride in as *scalar prefetch* operands
(``pltpu.PrefetchScalarGridSpec``), available to the BlockSpec index
maps **before** the kernel body runs, so the K/V (and exact-mode mask)
index maps dereference slot ``(b, i, j)`` of ``kv_indices`` and the DMA engine only
ever fetches occupied tiles.  The grid shrinks to ``(BH, nqb, P)`` where
``P`` is the padded max occupancy — work scheduled, fetched, and
computed all scale with the occupied-tile count, not ``nqb·nkb``.
Padding slots repeat an already-resident index (see ``compact_kv_plan``)
— the Pallas pipeline skips the DMA when consecutive grid steps map to
the same block, so padding costs neither fetch nor compute (the body is
``pl.when``-gated on ``j < kv_counts[b, i]``).

Grid: k-slot innermost so the VMEM scratch accumulators (acc, m, l)
carry across the k sweep of one query block.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.blockmap import bisect_select

NEG_INF = -2.0 ** 30


def _acc_init(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def _finalize_out(acc_ref, l_ref):
    """Normalized output tile; rows with no admissible key (l == 0)
    emit zeros.  Shared by the prefill and decode kernels — their out
    refs differ only in leading block layout."""
    l = l_ref[...]
    return jnp.where(l > 0, acc_ref[...] / jnp.where(l > 0, l, 1.0), 0.0)


def _acc_finalize(o_ref, acc_ref, l_ref):
    o_ref[0] = _finalize_out(acc_ref, l_ref).astype(o_ref.dtype)


def _kernel(bm_ref, q_ref, k_ref, v_ref, mask_ref, o_ref,
            acc_ref, m_ref, l_ref, *, sm_scale: float, n_kb: int,
            exact: bool):
    b, qi, kj = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        _acc_init(acc_ref, m_ref, l_ref)

    occupied = bm_ref[(b * pl.num_programs(1) + qi) * n_kb + kj] != 0

    @pl.when(occupied)
    def _update():
        _flash_update(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
                      sm_scale=sm_scale,
                      tile_mask=mask_ref[0] if exact else None)

    @pl.when(kj == n_kb - 1)
    def _finalize():
        _acc_finalize(o_ref, acc_ref, l_ref)


def sata_block_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, block_map: jax.Array,
    mask: Optional[jax.Array] = None,
    *, q_block: int = 128, k_block: int = 128,
    sm_scale: Optional[float] = None, interpret: bool = False,
) -> jax.Array:
    """q: (BH, Sq, D); k/v: (BH, Sk, D) in SATA-sorted key order;
    block_map: (BH, Sq/q_block, Sk/k_block) bool/int;
    mask: optional (BH, Sq, Sk) element-level selection mask."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    assert sq % q_block == 0 and sk % k_block == 0, (sq, sk)
    nqb, nkb = sq // q_block, sk // k_block
    sm_scale = float(sm_scale if sm_scale is not None else 1.0 / np.sqrt(d))
    exact = mask is not None
    if mask is None:
        mask = jnp.ones((bh, 1, 1), dtype=jnp.int8)    # dummy, never read

    from jax.experimental.pallas import tpu as pltpu

    kernel = functools.partial(_kernel, sm_scale=sm_scale, n_kb=nkb,
                               exact=exact)
    # index maps receive (grid ids..., block-map ref): the map rides in
    # as a flattened scalar-prefetch operand (SMEM) — a (1, 1, 1) VMEM
    # block over (BH, nqb, nkb) breaks Mosaic's (8, 128) tiling rule
    mask_spec = (pl.BlockSpec((1, q_block, k_block),
                              lambda b, i, j, bm: (b, i, j)) if exact
                 else pl.BlockSpec((1, 1, 1), lambda b, i, j, bm: (b, 0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bh, nqb, nkb),
        in_specs=[
            pl.BlockSpec((1, q_block, d), lambda b, i, j, bm: (b, i, 0)),
            pl.BlockSpec((1, k_block, d), lambda b, i, j, bm: (b, j, 0)),
            pl.BlockSpec((1, k_block, d), lambda b, i, j, bm: (b, j, 0)),
            mask_spec,
        ],
        out_specs=pl.BlockSpec((1, q_block, d),
                               lambda b, i, j, bm: (b, i, 0)),
        scratch_shapes=[
            _vmem((q_block, d), jnp.float32),       # acc
            _vmem((q_block, 1), jnp.float32),       # running max m
            _vmem((q_block, 1), jnp.float32),       # running sum l
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        interpret=interpret,
    )(block_map.reshape(-1).astype(jnp.int32), q, k, v, mask)


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)


# ---------------------------------------------------------------------------
# Compacted grid: scalar-prefetch scheduling (skips fetch, not just compute)
# ---------------------------------------------------------------------------

def _flash_update(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, *,
                  sm_scale: float, tile_mask=None, threshold=None,
                  admissible=None):
    """One online-softmax accumulation step over the resident K/V tile.

    Selection is one of: ``tile_mask`` (precomputed element mask, exact
    mode), ``threshold`` (a (bq, 1) per-row top-k threshold — the tile
    mask is re-derived *in-kernel* with the bisect-consistent bf16
    compare, optionally AND-ed with ``admissible``), or neither (block
    mode: dense math inside the tile).
    """
    _flash_update_tile(q_ref[0], k_ref[0], v_ref[0], acc_ref, m_ref,
                       l_ref, sm_scale=sm_scale, tile_mask=tile_mask,
                       threshold=threshold, admissible=admissible)


def _flash_update_tile(q, k, v, acc_ref, m_ref, l_ref, *,
                       sm_scale: float, tile_mask=None, threshold=None,
                       admissible=None):
    """Array-level core of ``_flash_update`` — shared with the decode
    kernel, whose block shapes carry a different leading layout.
    q: (bq, d); k/v: (bk, d); accumulators are VMEM refs."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale       # (bq, bk)
    if threshold is not None:
        assert tile_mask is None
        tile_mask = bisect_select(s, threshold)              # (bq, bk)
        if admissible is not None:
            tile_mask = tile_mask & admissible
    if tile_mask is not None:
        s = jnp.where(tile_mask, s, NEG_INF)
    m_prev = m_ref[...]                            # (bq, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                         # (bq, bk)
    if tile_mask is not None:
        # a row fully masked so far has s == m_new == NEG_INF, where the
        # finite sentinel gives exp(0) = 1, not 0 — zero masked entries
        # explicitly so such rows keep l == 0 and finalize to zeros.
        p = jnp.where(tile_mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)                # (bq, 1)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _compact_kernel(idx_ref, cnt_ref, q_ref, k_ref, v_ref, mask_ref,
                    thr_ref, qpos_ref, kpos_ref, o_ref,
                    acc_ref, m_ref, l_ref, *, sm_scale: float, n_slots: int,
                    select: str, causal: bool):
    b, qi, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        _acc_init(acc_ref, m_ref, l_ref)

    # Slots past the row's occupancy count are padding: their index maps
    # re-reference an already-resident tile (no fetch) and the body is
    # skipped entirely (no compute).
    @pl.when(j < cnt_ref[b * pl.num_programs(1) + qi])
    def _update():
        threshold = admissible = tile_mask = None
        if causal and select != "mask":
            # k_pos rides in per K-tile through the same prefetched
            # index map as K itself, so causality survives any key
            # permutation.  (Exact mode bakes causality into the mask.)
            qp = qpos_ref[0]                       # (bq, 1) int32
            kp = kpos_ref[0]                       # (bk, 1) int32
            admissible = jnp.transpose(kp) <= qp   # (bq, bk)
        if select == "mask":
            tile_mask = mask_ref[0]
        elif select == "threshold":
            threshold = thr_ref[0]                 # (bq, 1)
        else:
            # block mode: dense math inside the tile, but a causal
            # request must still gate future keys.
            tile_mask = admissible
            admissible = None
        _flash_update(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
                      sm_scale=sm_scale, tile_mask=tile_mask,
                      threshold=threshold, admissible=admissible)

    @pl.when(j == n_slots - 1)
    def _finalize():
        _acc_finalize(o_ref, acc_ref, l_ref)


def sata_block_attention_compact(
    q: jax.Array, k: jax.Array, v: jax.Array,
    kv_indices: jax.Array, kv_counts: jax.Array,
    mask: Optional[jax.Array] = None,
    thresholds: Optional[jax.Array] = None,
    q_pos: Optional[jax.Array] = None,
    k_pos: Optional[jax.Array] = None,
    *, causal: bool = False, q_block: int = 128, k_block: int = 128,
    sm_scale: Optional[float] = None, interpret: bool = False,
) -> jax.Array:
    """Compacted-grid SATA attention (see module docstring).

    q: (BH, Sq, D); k/v: (BH, Sk, D) in SATA-sorted key order;
    kv_indices: (BH, Sq/q_block, P) int32 occupied k-block indices,
    padded per ``core.blockmap.compact_kv_plan``;
    kv_counts:  (BH, Sq/q_block) int32 occupancy per q-block row.

    Selection — exactly one of:
      * ``mask``       (BH, Sq, Sk) element-level mask (exact mode; the
        quadratic operand the chunked pipeline exists to avoid);
      * ``thresholds`` (BH, Sq, 1) fp32 per-row top-k thresholds
        (threshold mode): the tile mask is recomputed in-kernel as
        ``bf16(score) >= bf16(thr)``; with ``causal=True``, ``q_pos``
        (BH, Sq, 1) / ``k_pos`` (BH, Sk, 1) int32 token positions (in
        the kernel's K layout order) gate it so only admissible keys
        count.  Only O(S) selection state ever reaches the kernel.
      * neither — block mode (dense math inside occupied tiles); with
        ``causal=True`` the position operands still gate future keys,
        so a causal request never leaks across the diagonal tiles.
    """
    from jax.experimental.pallas import tpu as pltpu

    bh, sq, d = q.shape
    sk = k.shape[1]
    assert sq % q_block == 0 and sk % k_block == 0, (sq, sk)
    nqb = sq // q_block
    n_slots = kv_indices.shape[-1]
    assert kv_indices.shape[:2] == (bh, nqb), (kv_indices.shape, bh, nqb)
    assert kv_counts.shape == (bh, nqb), (kv_counts.shape, bh, nqb)
    assert mask is None or thresholds is None, \
        "mask and thresholds are mutually exclusive selection modes"
    if n_slots == 0:
        # entirely-empty plan (pad_to=0): a zero-extent grid dim would
        # never run the kernel, leaving o_ref unwritten — the attention
        # of a row with no admissible key is zeros by definition.
        return jnp.zeros((bh, sq, d), q.dtype)
    sm_scale = float(sm_scale if sm_scale is not None else 1.0 / np.sqrt(d))
    select = ("mask" if mask is not None
              else "threshold" if thresholds is not None else "none")
    # exact mode bakes causality into the mask; threshold AND block mode
    # both need positions to honor a causal request in-kernel
    use_pos = causal and select != "mask"
    if use_pos:
        assert q_pos is not None and k_pos is not None, \
            "causal threshold/block mode needs q_pos/k_pos"
        assert q_pos.shape == (bh, sq, 1), q_pos.shape
        assert k_pos.shape == (bh, sk, 1), k_pos.shape
    dummy3 = jnp.zeros((1, 1, 1), jnp.int8)
    if mask is None:
        mask = dummy3                                  # never read
    if thresholds is None:
        thresholds = jnp.zeros((1, 1, 1), jnp.float32)
    if not use_pos:
        q_pos = k_pos = jnp.zeros((1, 1, 1), jnp.int32)
    if thresholds.shape != (1, 1, 1):
        assert thresholds.shape == (bh, sq, 1), thresholds.shape

    # index maps receive (grid ids..., *scalar-prefetch refs).  The plan
    # rides in flattened to 1-D: SMEM pads a 3-D scalar array's minor
    # dim to 128 words, which at serving widths (BH·nqb rows) overflows
    # the 1 MiB scalar memory.
    def _slot(b, i, j):
        return (b * nqb + i) * n_slots + j

    def kv_map(b, i, j, idx_ref, cnt_ref):
        return (b, idx_ref[_slot(b, i, j)], 0)

    def q_row_map(b, i, j, idx_ref, cnt_ref):
        return (b, i, 0)

    def _dummy_map(b, i, j, idx_ref, cnt_ref):
        return (0, 0, 0)

    dummy_spec = pl.BlockSpec((1, 1, 1), _dummy_map)
    mask_spec = (
        pl.BlockSpec((1, q_block, k_block),
                     lambda b, i, j, idx_ref, cnt_ref:
                     (b, i, idx_ref[_slot(b, i, j)])) if select == "mask"
        else dummy_spec)
    thr_spec = (pl.BlockSpec((1, q_block, 1), q_row_map)
                if select == "threshold" else dummy_spec)
    qpos_spec = (pl.BlockSpec((1, q_block, 1), q_row_map)
                 if use_pos else dummy_spec)
    kpos_spec = (pl.BlockSpec((1, k_block, 1), kv_map)
                 if use_pos else dummy_spec)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bh, nqb, n_slots),
        in_specs=[
            pl.BlockSpec((1, q_block, d), q_row_map),
            pl.BlockSpec((1, k_block, d), kv_map),
            pl.BlockSpec((1, k_block, d), kv_map),
            mask_spec,
            thr_spec,
            qpos_spec,
            kpos_spec,
        ],
        out_specs=pl.BlockSpec((1, q_block, d), q_row_map),
        scratch_shapes=[
            _vmem((q_block, d), jnp.float32),       # acc
            _vmem((q_block, 1), jnp.float32),       # running max m
            _vmem((q_block, 1), jnp.float32),       # running sum l
        ],
    )
    kernel = functools.partial(_compact_kernel, sm_scale=sm_scale,
                               n_slots=n_slots, select=select,
                               causal=use_pos)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        interpret=interpret,
    )(kv_indices.reshape(-1).astype(jnp.int32),
      kv_counts.reshape(-1).astype(jnp.int32),
      q, k, v, mask, thresholds.astype(jnp.float32),
      q_pos.astype(jnp.int32), k_pos.astype(jnp.int32))
