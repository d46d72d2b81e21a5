"""SATA decode gather kernel — scalar-prefetch selective fetch over the
KV cache for single-token decode.

Prefill's compacted grid walks ``(BH, nqb, P)``; at decode there is one
query *token* per slot, so the natural tile is the **GQA group**: the
``G = H // KV`` query heads that share a KV head attend the same cached
K/V blocks, giving a ``(G, D)`` q tile per ``(batch, kv_head)`` row and
a grid of ``(B·KV, P)`` — one slot per *selected* k-block, exactly the
incremental plan (``core/decode_plan.py``) maintains.

Scalar-prefetch operands (available to the BlockSpec index maps before
the body runs, so the DMA engine only ever touches planned tiles; each
rides in flattened to 1-D, since SMEM pads a 2-D scalar array's minor
dim to 128 words):

  kv_indices (B·KV, P) int32 — ascending selected k-block indices
                              (``compact_kv_plan`` padding: slots past
                              the count re-reference the resident block
                              — no fetch, and the body is skipped);
  kv_counts  (B·KV,)   int32 — live slots per row;
  pos        (B,)      int32 — per-slot decode positions: keys at
                              ``token > pos[b]`` are masked in-body, so
                              ragged slot lengths and freshly-claimed
                              (reset) slots never read stale cache.

K/V stay in the serving cache layout ``(B, S, KV, D)``: the kernel views
them as ``(B, S, KV·D)`` (a free reshape) and the index maps slice a
``(k_block, D)`` tile whose column-block index is the kv head, so no
head-expanded or transposed copy of the cache is ever materialized.
The view keeps the block's last two dims at ``(k_block, D)`` — a
``(k_block, 1, D)`` block over the 4-D cache would put a 1 against
``KV`` in the second-minor dim, which Mosaic's (8, 128) tiling rule
refuses.  On the chip ``D`` must be a multiple of 128.

**Paged cache** (``sata_decode_attention_paged_kernel``): the serving
cache may instead live in a global page pool ``(n_pages, page, KV, D)``
with a per-slot page table (``core/paging.py``).  Because the plan's
block edge equals the page size, the ONLY change is one more scalar-
prefetch operand — the page table — and a K/V index map that
dereferences it: ``physical = table[slot, kv_indices[row, j]]``.  The
grid, the flash inner loop, and the in-body masks are byte-for-byte the
same kernel (positions stay *logical*: ``kv_indices`` holds logical
page ids, so causality masking never sees physical placement).

Selection inside a fetched tile is threshold mode only: the element
mask is re-derived as ``bf16(score) >= bf16(thr)`` (the bisect predicate
shared with prefill) AND ``token <= pos``.  With a full re-plan every
step the output is bitwise equal to dense top-k (bisect) decode: a tile
whose every entry is masked contributes ``p = 0`` and leaves the online
softmax state untouched, so skipping it is exact.

The kernel is **summary-backend agnostic**: it consumes only the plan's
``kv_indices``/``kv_counts``/thresholds, never the block summaries, so
the fp32 and int8 summary backends (and the exact vs sketch re-plan
modes) change which blocks get planned — not how a planned block is
attended.  The plan-side traffic those backends save is accounted in
``kernels.ops.decode_fetch_stats`` (dtype- and mode-aware), not here.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.blockmap import bisect_select
from repro.kernels.sata_attention import (_acc_init, _finalize_out,
                                          _flash_update_tile, _vmem)


def _decode_kernel(idx_ref, cnt_ref, pos_ref, q_ref, k_ref, v_ref,
                   thr_ref, o_ref, acc_ref, m_ref, l_ref, *,
                   sm_scale: float, n_slots: int, k_block: int,
                   n_kv: int):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        _acc_init(acc_ref, m_ref, l_ref)

    @pl.when(j < cnt_ref[i])
    def _update():
        q = q_ref[0, 0]                            # (G, D)
        k = k_ref[0]                               # (k_block, D)
        v = v_ref[0]
        # global key positions of the resident tile gate validity: the
        # plan may include the partially-written tail block, and padded
        # slots of *shorter* ragged rows must not see future tokens.
        kpos = idx_ref[i * n_slots + j] * k_block + \
            jax.lax.broadcasted_iota(jnp.int32, (1, k_block), 1)
        admissible = kpos <= pos_ref[i // n_kv]              # (1, k_block)
        _flash_update_tile(q, k, v, acc_ref, m_ref, l_ref,
                           sm_scale=sm_scale, threshold=thr_ref[0, 0],
                           admissible=admissible)

    @pl.when(j == n_slots - 1)
    def _finalize():
        o_ref[0, 0] = _finalize_out(acc_ref, l_ref).astype(o_ref.dtype)


def sata_decode_attention_kernel(
    q: jax.Array, k: jax.Array, v: jax.Array,
    kv_indices: jax.Array, kv_counts: jax.Array,
    thresholds: jax.Array, pos: jax.Array,
    *, k_block: int = 128, sm_scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """q: (B, KV, G, D) grouped query rows; k/v: (B, S, KV, D) cache;
    kv_indices: (B, KV, P) int32; kv_counts: (B, KV) int32;
    thresholds: (B, KV, G, 1) fp32 per-row top-k thresholds;
    pos: (B,) int32 per-slot positions.  Returns (B, KV, G, D)."""
    from jax.experimental.pallas import tpu as pltpu

    b, n_kv, g, d = q.shape
    s = k.shape[1]
    assert k.shape == (b, s, n_kv, d), (k.shape, q.shape)
    assert s % k_block == 0, (s, k_block)
    p = kv_indices.shape[-1]
    assert kv_indices.shape == (b, n_kv, p), kv_indices.shape
    assert kv_counts.shape == (b, n_kv), kv_counts.shape
    assert thresholds.shape == (b, n_kv, g, 1), thresholds.shape
    assert pos.shape == (b,), pos.shape
    if p == 0:
        return jnp.zeros((b, n_kv, g, d), q.dtype)
    sm_scale = float(sm_scale if sm_scale is not None else 1.0 / np.sqrt(d))

    def q_map(i, j, idx_ref, cnt_ref, pos_ref):
        return (i // n_kv, i % n_kv, 0, 0)

    def kv_map(i, j, idx_ref, cnt_ref, pos_ref):
        # K/V viewed as (B, S, KV·D): the block's column index IS the
        # kv head, so a (k_block, D) tile is one head's rows
        return (i // n_kv, idx_ref[i * p + j], i % n_kv)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b * n_kv, p),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), q_map),
            pl.BlockSpec((1, k_block, d), kv_map),
            pl.BlockSpec((1, k_block, d), kv_map),
            pl.BlockSpec((1, 1, g, 1), q_map),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), q_map),
        scratch_shapes=[
            _vmem((g, d), jnp.float32),             # acc
            _vmem((g, 1), jnp.float32),             # running max m
            _vmem((g, 1), jnp.float32),             # running sum l
        ],
    )
    kernel = functools.partial(_decode_kernel, sm_scale=sm_scale,
                               n_slots=p, k_block=k_block, n_kv=n_kv)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_kv, g, d), q.dtype),
        interpret=interpret,
    )(kv_indices.reshape(-1).astype(jnp.int32),
      kv_counts.reshape(-1).astype(jnp.int32),
      pos.astype(jnp.int32),
      q, k.reshape(b, s, n_kv * d), v.reshape(b, s, n_kv * d),
      thresholds.astype(jnp.float32))


def _paged_decode_kernel(idx_ref, cnt_ref, pos_ref, tbl_ref, *args, **kw):
    """Paged body == contiguous body: the page table is consumed only by
    the BlockSpec index maps, never inside the kernel."""
    del tbl_ref
    _decode_kernel(idx_ref, cnt_ref, pos_ref, *args, **kw)


def sata_decode_attention_paged_kernel(
    q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
    page_table: jax.Array, kv_indices: jax.Array, kv_counts: jax.Array,
    thresholds: jax.Array, pos: jax.Array,
    *, sm_scale: Optional[float] = None, interpret: bool = False,
) -> jax.Array:
    """Decode gather kernel over the paged pool: q (B, KV, G, D);
    k_pages/v_pages (n_pages, page, KV, D); page_table (B, max_pages)
    int32 (logical→physical); kv_indices (B, KV, P) int32 *logical*
    page ids; kv_counts (B, KV); thresholds (B, KV, G, 1) fp32;
    pos (B,).  Returns (B, KV, G, D).  The k-block edge IS the page
    size."""
    from jax.experimental.pallas import tpu as pltpu

    b, n_kv, g, d = q.shape
    n_pages, page, kvh, dk = k_pages.shape
    assert (kvh, dk) == (n_kv, d), (k_pages.shape, q.shape)
    assert v_pages.shape == k_pages.shape
    p = kv_indices.shape[-1]
    assert kv_indices.shape == (b, n_kv, p), kv_indices.shape
    assert kv_counts.shape == (b, n_kv), kv_counts.shape
    assert thresholds.shape == (b, n_kv, g, 1), thresholds.shape
    assert page_table.shape[0] == b, (page_table.shape, b)
    max_pages = page_table.shape[1]
    assert pos.shape == (b,), pos.shape
    if p == 0:
        return jnp.zeros((b, n_kv, g, d), q.dtype)
    sm_scale = float(sm_scale if sm_scale is not None else 1.0 / np.sqrt(d))

    def q_map(i, j, idx_ref, cnt_ref, pos_ref, tbl_ref):
        return (i // n_kv, i % n_kv, 0, 0)

    def kv_map(i, j, idx_ref, cnt_ref, pos_ref, tbl_ref):
        # the one paged-vs-contiguous difference: logical plan entry →
        # physical page through the slot's table row
        return (tbl_ref[(i // n_kv) * max_pages + idx_ref[i * p + j]],
                0, i % n_kv)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b * n_kv, p),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), q_map),
            pl.BlockSpec((1, page, d), kv_map),
            pl.BlockSpec((1, page, d), kv_map),
            pl.BlockSpec((1, 1, g, 1), q_map),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), q_map),
        scratch_shapes=[
            _vmem((g, d), jnp.float32),             # acc
            _vmem((g, 1), jnp.float32),             # running max m
            _vmem((g, 1), jnp.float32),             # running sum l
        ],
    )
    kernel = functools.partial(_paged_decode_kernel, sm_scale=sm_scale,
                               n_slots=p, k_block=page, n_kv=n_kv)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_kv, g, d), q.dtype),
        interpret=interpret,
    )(kv_indices.reshape(-1).astype(jnp.int32),
      kv_counts.reshape(-1).astype(jnp.int32),
      pos.astype(jnp.int32),
      page_table.reshape(-1).astype(jnp.int32),
      q, k_pages.reshape(n_pages, page, n_kv * d),
      v_pages.reshape(n_pages, page, n_kv * d),
      thresholds.astype(jnp.float32))
