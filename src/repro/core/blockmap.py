"""SATA → block-sparse tile maps (the TPU-native execution plan).

The MXU consumes 128×128 (or block-shaped) dense tiles — element-level
sparsity buys nothing.  SATA's key sorting concentrates each query's
selected keys into a contiguous range of the sorted order, so after
permuting K/V by ``kid`` and grouping queries by class, whole
(q_block × k_block) tiles of the score matrix become empty and can be
skipped.  This module derives that plan *in-graph* (pure jnp, jittable,
vmappable over heads) for consumption by ``kernels/sata_attention``.

Outputs per head:
  kv_order  (N,)  int32   — SATA sorted key permutation (Gram-greedy)
  q_order   (N,)  int32   — queries grouped HEAD | GLOB | TAIL
  block_map (nqb, nkb) bool — tile occupancy after both permutations

``compact_kv_plan`` turns the boolean map into the *scheduled* form the
compacted-grid kernel consumes: per (bh, q_block) a padded ascending
list of occupied k-block indices plus a count, so the Pallas grid walks
only occupied slots and the BlockSpec index maps never point the DMA
engine at an empty tile.

The plan-from-chunks constructors (``occupancy_from_score_chunk``,
``occupancy_from_scores_chunked``, ``compact_plan_from_chunks``) build
the same schedule from *streamed* ``q_chunk × Sk`` score tiles and a
per-row top-k threshold, so neither the (BH, Sq, Sk) score tensor nor
the boolean mask is ever materialized — the selection state that
persists is O(Sq) thresholds plus the block-granular plan.
``occupancy_bound`` turns concrete plan statistics into the static
``max_kv_blocks`` bound jitted serving paths need for a compact grid.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sorting import sort_keys_jax


def query_order_from_sorted(sorted_mask: jax.Array, s_h: int) -> jax.Array:
    """Order queries (HEAD | GLOB | TAIL) and, *within* each class, by the
    centroid of their selected keys in sorted-key space.

    The class bands are the paper's classification; the centroid refine-
    ment is a beyond-paper extension: two HEAD queries whose key sets sit
    at sorted positions ~10 vs ~120 land in different q-blocks, so their
    (q_block × k_block) tiles empty out — at MXU granularity the 3-class
    ordering alone leaves blocks occupied (§Perf documents the delta).
    sorted_mask: (..., N_q, N_k) bool, already column-permuted by kid."""
    n_k = sorted_mask.shape[-1]
    s_h = min(int(s_h), n_k // 2)
    first = sorted_mask[..., :s_h].any(axis=-1)
    last = sorted_mask[..., n_k - s_h:].any(axis=-1)
    # class rank: HEAD=0 (no tail access), GLOB=1 (both), TAIL=2
    rank = jnp.where(~last, 0, jnp.where(first, 1, 2)).astype(jnp.float32)
    m = sorted_mask.astype(jnp.float32)
    pos = jnp.arange(n_k, dtype=jnp.float32)
    centroid = (m * pos).sum(-1) / jnp.clip(m.sum(-1), 1.0)   # (..., N_q)
    key = rank * (2.0 * n_k) + centroid
    return jnp.argsort(key, axis=-1, stable=True).astype(jnp.int32)


def block_occupancy(mask: jax.Array, q_block: int, k_block: int) -> jax.Array:
    """(..., N_q/qb, N_k/kb) bool — any selected pair inside each tile."""
    *b, n_q, n_k = mask.shape
    nqb, nkb = n_q // q_block, n_k // k_block
    m = mask.reshape(*b, nqb, q_block, nkb, k_block)
    return m.any(axis=(-3, -1))


def sata_block_plan(mask: jax.Array, q_block: int, k_block: int,
                    s_h_frac: float = 0.5, seed: int = 0
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Full in-graph SATA plan: (kv_order, q_order, block_map).

    mask: (..., N_q, N_k) bool top-k selection mask.
    """
    n_k = mask.shape[-1]
    kv_order = sort_keys_jax(mask, seed=seed)                      # (..., N_k)
    sorted_mask = jnp.take_along_axis(mask, kv_order[..., None, :], axis=-1)
    s_h = max(1, int(s_h_frac * n_k))
    q_order = query_order_from_sorted(sorted_mask, s_h)            # (..., N_q)
    permuted = jnp.take_along_axis(sorted_mask, q_order[..., :, None], axis=-2)
    block_map = block_occupancy(permuted, q_block, k_block)
    return kv_order, q_order, block_map


def compact_kv_plan(block_map: jax.Array, pad_to: int | None = None,
                    truncate: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Compact each (…, q_block) row of ``block_map`` to the list of
    occupied k-block indices — the scalar-prefetch schedule for the
    compacted-grid kernel.

    block_map: (..., nqb, nkb) bool/int tile occupancy.
    Returns ``(kv_indices (..., nqb, P) int32, kv_counts (..., nqb) int32)``
    with ``P = pad_to or nkb``.

    Slot ``j < count`` holds the j-th occupied k-block index (ascending).
    Padding slots are chosen so the kernel's K/V index map never points
    the DMA at a tile that is not already part of the fetched set:

      * rows with ≥1 occupied tile repeat their *last* occupied index —
        consecutive grid steps then map to the block already resident in
        VMEM and the Pallas pipeline issues no new fetch;
      * fully-empty rows inherit the last occupied index of the nearest
        preceding non-empty row, so the row-boundary transition is a
        no-op re-reference rather than a fetch of an unoccupied tile;
      * *leading* empty rows (no preceding non-empty row) take the
        **first** occupied index of the first non-empty row — the grid's
        unavoidable first-step fetch then lands exactly on the tile that
        row will need, so it costs nothing extra.

    A batch entry whose map is entirely empty (no occupied tile at all)
    falls back to index 0 — some tile must back the very first grid step.

    ``pad_to`` statically narrows the slot dimension (and hence the
    kernel grid): callers that know ``counts.max()`` concretely (eager
    benchmarks, a host-side planner) pass it so grid size scales with the
    occupied-tile count instead of ``nkb``.  It must be ≥ the true max
    count or occupied tiles would be dropped — validated here whenever
    the map is concrete; under jit the caller must pass a static
    over-estimate (the safe default ``None`` keeps the full ``nkb``).
    ``truncate=True`` opts into dropping instead: each row keeps its
    first ``pad_to`` occupied k-blocks (ascending) and counts are
    clamped — the explicit approximation a sub-100-percentile
    ``occupancy_bound`` implies.
    """
    bm = block_map.astype(bool)
    *_, nqb, nkb = bm.shape
    counts = bm.sum(-1).astype(jnp.int32)                       # (..., nqb)
    if pad_to is not None:
        if not truncate and not isinstance(counts, jax.core.Tracer) \
                and pad_to < int(counts.max(initial=0)):
            raise ValueError(
                f"pad_to={pad_to} < max occupancy "
                f"{int(counts.max(initial=0))}: occupied tiles would be "
                f"silently dropped (pass truncate=True to opt in)")
        # clamp BEFORE deriving the padding fill: `last`/`fill` must
        # reference a tile the truncated schedule actually fetches, or
        # empty-row padding would DMA a tile no slot computes on.
        counts = jnp.minimum(counts, pad_to)
    # stable sort of (not occupied) → occupied indices first, ascending
    order = jnp.argsort(~bm, axis=-1, stable=True).astype(jnp.int32)
    last = jnp.take_along_axis(
        order, jnp.maximum(counts - 1, 0)[..., None], axis=-1)[..., 0]
    # forward-fill `last` across q rows for empty rows; leading empties
    # borrow from the first non-empty row.
    valid = counts > 0
    rowid = jnp.where(valid, jnp.arange(nqb, dtype=jnp.int32), -1)
    prev_valid = jax.lax.cummax(rowid, axis=rowid.ndim - 1)     # (..., nqb)
    first_valid = jnp.argmax(valid, axis=-1)[..., None]
    fill_fwd = jnp.take_along_axis(last, jnp.maximum(prev_valid, 0), axis=-1)
    first_occ = order[..., 0]                   # first occupied per row
    fill_bwd = jnp.take_along_axis(first_occ, first_valid, axis=-1)
    fill = jnp.where(prev_valid >= 0, fill_fwd, fill_bwd)       # (..., nqb)
    fill = jnp.where(valid.any(-1, keepdims=True), fill, 0)
    slot = jnp.arange(nkb, dtype=jnp.int32)
    kv_indices = jnp.where(slot < counts[..., None], order, fill[..., None])
    if pad_to is not None:
        kv_indices = kv_indices[..., :pad_to]
    return kv_indices, counts


# ---------------------------------------------------------------------------
# Plan-from-chunks: selection → occupancy → compact plan without ever
# materializing the (BH, Sq, Sk) score tensor or boolean mask
# ---------------------------------------------------------------------------

def bisect_select(scores: jax.Array, threshold: jax.Array) -> jax.Array:
    """THE selection predicate: ``bf16(score) >= bf16(threshold)`` — the
    exact compare ``kth_largest_bisect``'s counting pass runs, so its
    ``count >= k`` loop invariant transfers to whoever applies it.
    Every consumer (the bisect itself, mask construction, occupancy
    reduction, the threshold-mode kernel, the chunked differentiation
    rule) MUST call this one helper: a drifted reimplementation would
    let the occupancy map and the kernel disagree about which tiles
    hold work, silently dropping selected keys.

    Both sides round to bf16 (``bf16_round``) and compare in fp32: v5e
    has no bf16 VPU and Mosaic refuses a bf16 ``cmpf`` inside a kernel."""
    return bf16_round(scores) >= bf16_round(threshold)


def bf16_round(x: jax.Array) -> jax.Array:
    """``x`` rounded to the nearest bf16 (ties to even), kept in fp32.

    Rounded on the bits, so XLA and a Mosaic kernel round alike.  With
    a float convert round trip in its place, a jitted XLA program on a
    v5e selected other keys than the same predicate inside a kernel,
    and the selection predicate must agree bit for bit on both sides.
    ``lax.reduce_precision`` would say this in one call, but the Pallas
    TPU lowering (jax 0.9.0) has no rule for it.  Equal to
    ``x.astype(bfloat16).astype(float32)`` for every non-NaN value on
    the CPU."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    lsb = jax.lax.shift_right_logical(bits, jnp.uint32(16)) & jnp.uint32(1)
    bits = (bits + jnp.uint32(0x7FFF) + lsb) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def occupancy_from_score_chunk(scores_chunk: jax.Array, thr_chunk: jax.Array,
                               admissible: jax.Array, q_block: int,
                               k_block: int) -> jax.Array:
    """Tile-level occupancy reduction for one streamed score chunk.

    scores_chunk: (BH, C, Sk) fp32 *raw* (unmasked) scaled scores;
    thr_chunk:    (BH, C, 1) fp32 per-row top-k threshold
                  (``kth_largest_bisect`` output);
    admissible:   (BH|1, C, Sk) bool causal/validity mask.
    Returns (BH, C/q_block, Sk/k_block) bool tile occupancy.

    The compare is the bisect-consistent bf16 one (see
    ``kth_largest_bisect``): an admissible entry is selected iff
    ``bf16(score) >= bf16(thr)`` — the exact predicate the threshold-mode
    kernel re-evaluates per tile, so the occupancy map and the kernel
    agree on which tiles hold work.
    """
    bh, c, sk = scores_chunk.shape
    sel = bisect_select(scores_chunk, thr_chunk) & admissible
    return sel.reshape(bh, c // q_block, q_block,
                       sk // k_block, k_block).any(axis=(2, 4))


def resolve_sel_chunk(chunk: Optional[int], s: int, q_block: int) -> int:
    """Largest multiple of ``q_block`` that is <= ``chunk`` (default
    ``q_block``) and divides ``s`` — the streaming granularity of the
    chunked selection passes.  Requires ``s % q_block == 0``."""
    assert s % q_block == 0, (s, q_block)
    c = min(chunk or q_block, s)
    c = max(q_block, (c // q_block) * q_block)
    while s % c:
        c -= q_block
    return c


def stream_score_chunks(q: jax.Array, k: jax.Array, fn, *, chunk: int,
                        sm_scale: Optional[float] = None,
                        causal: bool = True,
                        q_pos: Optional[jax.Array] = None,
                        k_pos: Optional[jax.Array] = None,
                        extras: Tuple[jax.Array, ...] = (),
                        remat: bool = False):
    """The one streaming loop every chunked-selection consumer shares:
    materialize one (BH, chunk, Sk) scaled score tile + its causal
    admissibility mask at a time and apply
    ``fn(scores_chunk, admissible, *extra_chunks)``.

    ``extras`` are (BH, Sq, …) arrays chunked alongside ``q`` (e.g. the
    per-row thresholds on a re-stream).  ``remat=True`` wraps each chunk
    in ``jax.checkpoint`` so a differentiated caller recomputes the tile
    in backward instead of saving it.  Returns ``fn``'s outputs stacked
    on a leading (Sq/chunk) axis.

    Centralized on purpose: the bisect-consistency contract (score
    scaling, NEG_INF admissibility, one tile live at a time) must stay
    identical between threshold pass, occupancy re-stream, and the
    chunked differentiation rule — one loop means they cannot drift.
    """
    bh, s, d = q.shape
    sk = k.shape[1]
    assert s % chunk == 0, (s, chunk)
    scale = float(sm_scale if sm_scale is not None else 1.0 / np.sqrt(d))
    n = s // chunk
    q_pos = (jnp.arange(s, dtype=jnp.int32) if q_pos is None
             else q_pos.astype(jnp.int32))
    kp = (jnp.arange(sk, dtype=jnp.int32) if k_pos is None
          else k_pos.astype(jnp.int32))
    qs = jnp.moveaxis(q.reshape(bh, n, chunk, d), 1, 0)
    ps = q_pos.reshape(n, chunk)
    exs = tuple(jnp.moveaxis(e.reshape(bh, n, chunk, *e.shape[2:]), 1, 0)
                for e in extras)

    def one(args):
        q_c, p_c, *e_c = args
        sc = jnp.einsum("bqd,bkd->bqk", q_c, k,
                        preferred_element_type=jnp.float32) * scale
        if causal:
            adm = (kp[None, :] <= p_c[:, None])[None]
        else:
            adm = jnp.ones((1, chunk, sk), dtype=bool)
        return fn(sc, adm, *e_c)

    if remat:
        one = jax.checkpoint(one)
    return jax.lax.map(one, (qs, ps) + exs)


def occupancy_from_scores_chunked(
    q: jax.Array, k: jax.Array, thresholds: jax.Array, *,
    q_block: int, k_block: int, sm_scale: Optional[float] = None,
    causal: bool = True, q_pos: Optional[jax.Array] = None,
    k_pos: Optional[jax.Array] = None, chunk: Optional[int] = None,
) -> jax.Array:
    """Re-stream ``q_chunk × Sk`` score tiles against precomputed per-row
    thresholds and emit the (BH, nqb, nkb) tile occupancy map directly
    from tile-level reductions — the boolean (BH, Sq, Sk) mask is never
    built.  Peak live selection state is one (BH, chunk, Sk) tile.

    q: (BH, Sq, D); k: (BH, Sk, D); thresholds: (BH, Sq, 1) fp32.
    """
    bh, sq, _ = q.shape
    sk = k.shape[1]
    assert sk % k_block == 0, (sk, k_block)
    chunk = resolve_sel_chunk(chunk, sq, q_block)
    occ = stream_score_chunks(
        q, k,
        lambda sc, adm, t_c: occupancy_from_score_chunk(sc, t_c, adm,
                                                        q_block, k_block),
        chunk=chunk, sm_scale=sm_scale, causal=causal, q_pos=q_pos,
        k_pos=k_pos, extras=(thresholds,))          # (n, BH, chunk/qb, nkb)
    return jnp.moveaxis(occ, 0, 1).reshape(bh, sq // q_block, sk // k_block)


def compact_plan_from_chunks(
    q: jax.Array, k: jax.Array, thresholds: jax.Array, *,
    q_block: int, k_block: int, sm_scale: Optional[float] = None,
    causal: bool = True, q_pos: Optional[jax.Array] = None,
    k_pos: Optional[jax.Array] = None, chunk: Optional[int] = None,
    pad_to: Optional[int] = None, truncate: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Selection → compact schedule in one call, mask-free: streamed
    occupancy (``occupancy_from_scores_chunked``) followed by
    ``compact_kv_plan``.  Returns (block_map, kv_indices, kv_counts)."""
    bm = occupancy_from_scores_chunked(
        q, k, thresholds, q_block=q_block, k_block=k_block,
        sm_scale=sm_scale, causal=causal, q_pos=q_pos, k_pos=k_pos,
        chunk=chunk)
    kv_indices, kv_counts = compact_kv_plan(bm, pad_to=pad_to,
                                            truncate=truncate)
    return bm, kv_indices, kv_counts


def occupancy_bound(kv_counts, pct: float = 100.0) -> int:
    """Static per-row occupancy bound from concrete plan statistics.

    ``kv_counts``: (…, nqb) int occupied-k-block counts from a
    calibration run (``compact_kv_plan`` / ``compact_plan_from_chunks``).
    Returns ``ceil(pct-th percentile)`` as a plain int, floored at 1 —
    the value to pass as ``max_kv_blocks`` so *jitted* serving paths get
    a compact grid without a concrete mask in hand.

    ``pct=100`` is exact (no tile ever dropped).  Lower percentiles
    trade tail rows for a smaller grid: a row whose occupancy exceeds
    the bound keeps its first ``bound`` occupied k-blocks (ascending)
    and drops the rest — pass ``truncate=True`` to ``compact_kv_plan``
    to opt into that approximation on concrete maps (under jit the
    validation cannot run and truncation is implicit).
    Host-side by design: raises on tracers (derive the bound offline,
    then bake it in as a static argument).
    """
    if isinstance(kv_counts, jax.core.Tracer):
        raise TypeError(
            "occupancy_bound needs concrete counts — run the planner on "
            "calibration data outside jit, then pass the result as the "
            "static max_kv_blocks")
    counts = np.asarray(kv_counts).reshape(-1)
    if counts.size == 0:
        return 1
    return max(1, int(np.ceil(np.percentile(counts, pct))))


def block_skip_fraction(block_map: jax.Array) -> jax.Array:
    """Fraction of (q_block × k_block) tiles with zero work."""
    return 1.0 - block_map.mean()


def fixed_occupancy_map(rng, bh: int, nqb: int, nkb: int, occ: int):
    """Host-side (numpy) random block map with exactly ``occ`` occupied
    k-blocks per (bh, q_row) — the concentrated regime SATA's key sort
    produces, and the shape the benchmarks use so the padded compact
    grid (`P = occ`) actually shrinks (a Bernoulli map almost surely has
    one fully-occupied row pinning P at ``nkb``)."""
    bm = np.zeros((bh, nqb, nkb), dtype=bool)
    for b in range(bh):
        for i in range(nqb):
            bm[b, i, rng.choice(nkb, size=occ, replace=False)] = True
    return bm


def identity_block_plan(mask: jax.Array, q_block: int, k_block: int
                        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Unsorted baseline: identity permutations + raw occupancy."""
    *b, n_q, n_k = mask.shape
    kv_order = jnp.broadcast_to(jnp.arange(n_k, dtype=jnp.int32), (*b, n_k))
    q_order = jnp.broadcast_to(jnp.arange(n_q, dtype=jnp.int32), (*b, n_q))
    return kv_order, q_order, block_occupancy(mask, q_block, k_block)
