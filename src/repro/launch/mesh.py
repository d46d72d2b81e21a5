"""Production mesh builders + the SATA scale-out shard_map wrappers.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before any jax initialization).

Scale-out (``XLA_FLAGS=--xla_force_host_platform_device_count=8`` gives
CI a simulated multi-device CPU mesh):

* **Sequence-sharded selection** (``sequence_sharded_attention``):
  queries shard along Sq; every selection reduction in
  ``select_thresholds_chunked`` is row-local, so each shard's
  thresholds/occupancy are *bitwise* the corresponding rows of the
  single-device run.  Each shard builds its own ``compact_kv_plan`` and
  halo-exchanges only the K/V tiles that plan selects — the compact
  per-shard tile buffers (and the fetch accounting) are
  plan-proportional.  The transport primitive here is an ``all_gather``
  standing in for the tile-granular RDMA a real interconnect issues
  (see the ring-collective pattern in the Pallas guide); what the
  epilogue *touches* is only the planned tiles.
* **Tensor-parallel decode** (``tensor_parallel_decode_step``): the
  decode plan state, KV cache and gather kernel are all per-(slot,
  KV-head) independent, so sharding over KV heads needs no collectives
  at all — ``plan_pspecs`` maps every plan leaf to its PartitionSpec
  and the kernel runs unchanged inside ``shard_map``.

These wrappers are EXPLICIT: they never install a global device
context, so ``attention.sata_decode_on``'s conservative
``mesh_installed()`` fallback (for paths that have no SPMD rule) is
not tripped by them.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType
from jax.sharding import PartitionSpec as P

from repro.core.blockmap import bisect_select, compact_kv_plan
from repro.core.selection import NEG_INF, select_thresholds_chunked


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod mesh: 16×16 (256 chips) single-pod, or 2×16×16 multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def data_axes(mesh) -> Tuple[str, ...]:
    """Axes over which the batch (and FSDP weight dims) shard."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def make_local_mesh():
    """Single-device mesh for CPU tests/examples."""
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def activate_mesh(mesh):
    """Context manager installing ``mesh`` for jit/sharding-constraint
    resolution."""
    return jax.set_mesh(mesh)


# ---------------------------------------------------------------------------
# SATA scale-out: explicit shard_map wrappers (module docstring)
# ---------------------------------------------------------------------------

def make_shard_mesh(n_shards: int, axis: str = "shard"):
    """1-D mesh over the first ``n_shards`` local devices — the unit the
    scale-out wrappers (and the forced-host-device CI mesh) run on."""
    devs = jax.devices()
    if len(devs) < n_shards:
        raise ValueError(
            f"need {n_shards} devices, have {len(devs)} — set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=N before "
            f"jax initializes to simulate a CPU mesh")
    return jax.sharding.Mesh(np.array(devs[:n_shards]), (axis,))


def _selection_plan_local(q, k, q_pos, *, k_sel: int, q_block: int,
                          k_block: int, sm_scale, causal: bool):
    """One shard's selection + plan: row-local thresholds/occupancy
    (bitwise the global rows) and the full-width compact schedule.
    ``pad_to`` stays ``None`` (P = nkb) so the sharded and single-device
    tile buffers have identical padded layout — the epilogue's masked
    reductions then add identically-placed exact zeros and parity is
    bitwise, not approximate."""
    thr, bm = select_thresholds_chunked(
        q, k, k_sel, q_pos=q_pos, causal=causal, sm_scale=sm_scale,
        q_block=q_block, k_block=k_block)
    kv_indices, kv_counts = compact_kv_plan(bm)
    return thr, bm, kv_indices, kv_counts


def _gather_plan_tiles(x, kv_indices, *, k_block: int):
    """Fetch only the planned tiles: x (BH, Sk, D) + indices
    (BH, nqb, P) → compact (BH, nqb, P·k_block, D) buffers plus the
    gathered token positions (BH, nqb, P·k_block)."""
    bh, sk, d = x.shape
    nkb = sk // k_block
    _, nqb, p = kv_indices.shape
    xt = x.reshape(bh, nkb, k_block, d)
    tiles = jax.vmap(lambda t, ix: t[ix])(xt, kv_indices)
    tok = (kv_indices[..., None] * k_block +
           jnp.arange(k_block)[None, None, None, :])
    return (tiles.reshape(bh, nqb, p * k_block, d),
            tok.reshape(bh, nqb, p * k_block))


def planned_tile_attention(q, k_tiles, v_tiles, tok, thr, kv_counts, *,
                           q_block: int, k_block: int, q_pos,
                           sm_scale=None):
    """Threshold-mode attention over the compact planned-tile buffers —
    the one epilogue BOTH the sharded path and the single-device
    reference run, so identical plans give bitwise-identical outputs.

    q: (BH, Sq, D); k_tiles/v_tiles: (BH, nqb, P·kb, D); tok:
    (BH, nqb, P·kb) gathered token positions; thr: (BH, Sq, 1);
    kv_counts: (BH, nqb); q_pos: (Sq,) global query positions.

    A token participates iff its slot is live (padding slots repeat
    real tiles — without the count mask they would double-count), it is
    causally admissible, and it passes the bisect-consistent selection
    predicate against its row's threshold.  All reductions are
    row-local.
    """
    bh, s, d = q.shape
    nqb = s // q_block
    t = k_tiles.shape[2]
    scale = float(sm_scale if sm_scale is not None else 1.0 / np.sqrt(d))
    qb = q.reshape(bh, nqb, q_block, d).astype(jnp.float32)
    sc = jnp.einsum("bnqd,bntd->bnqt", qb,
                    k_tiles.astype(jnp.float32),
                    preferred_element_type=jnp.float32) * scale
    slot = jnp.arange(t) // k_block                           # (P·kb,)
    live = slot[None, None, :] < kv_counts[..., None]         # (BH,nqb,P·kb)
    posr = q_pos.astype(jnp.int32).reshape(nqb, q_block)
    adm = live[:, :, None, :] & \
        (tok[:, :, None, :] <= posr[None, :, :, None])
    thr_r = thr.reshape(bh, nqb, q_block, 1)
    sel = bisect_select(sc, thr_r) & adm
    sc = jnp.where(sel, sc, NEG_INF)
    m = jnp.max(sc, axis=-1, keepdims=True)
    w = jnp.where(sel, jnp.exp(sc - m), 0.0)
    out = jnp.einsum("bnqt,bntd->bnqd", w, v_tiles.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    out = out / jnp.maximum(w.sum(-1, keepdims=True), 1e-30)
    return out.reshape(bh, s, d)


def sequence_local_attention(q, k, v, *, k_sel: int, q_block: int = 128,
                             k_block: int = 128, causal: bool = True,
                             sm_scale=None):
    """Single-device reference for ``sequence_sharded_attention``: the
    same selection → plan → tile-gather → epilogue pipeline with no
    mesh.  Returns (out, stats)."""
    s = q.shape[1]
    q_pos = jnp.arange(s, dtype=jnp.int32)
    thr, bm, idx, cnt = _selection_plan_local(
        q, k, q_pos, k_sel=k_sel, q_block=q_block, k_block=k_block,
        sm_scale=sm_scale, causal=causal)
    k_tiles, tok = _gather_plan_tiles(k, idx, k_block=k_block)
    v_tiles, _ = _gather_plan_tiles(v, idx, k_block=k_block)
    out = planned_tile_attention(q, k_tiles, v_tiles, tok, thr, cnt,
                                 q_block=q_block, k_block=k_block,
                                 q_pos=q_pos, sm_scale=sm_scale)
    return out, {"thresholds": thr, "block_map": bm, "kv_counts": cnt,
                 "fetched_tiles": cnt.sum()}


def sequence_sharded_attention(mesh, q, k, v, *, k_sel: int,
                               q_block: int = 128, k_block: int = 128,
                               causal: bool = True, sm_scale=None,
                               axis: Optional[str] = None):
    """Sequence-parallel selective attention on ``mesh``: q shards along
    Sq, K/V along Sk; each shard bisects its rows' thresholds
    (row-local ⇒ bitwise the global rows), builds its own compact plan,
    halo-exchanges only the planned K/V tiles into compact buffers, and
    runs the shared epilogue.  Output is bitwise equal to
    ``sequence_local_attention`` on one device.

    q: (BH, Sq, D); k/v: (BH, Sk, D).  Sq must tile by
    ``n_shards·q_block`` and Sk by ``n_shards·k_block``.  Returns
    ``(out, stats)`` with ``stats["fetched_tiles_per_shard"]`` the
    plan-proportional per-shard fetch the halo exchange materializes.
    """
    ax = axis or mesh.axis_names[0]
    n = mesh.shape[ax]
    bh, s, d = q.shape
    sk = k.shape[1]
    assert s % (n * q_block) == 0, (s, n, q_block)
    assert sk % (n * k_block) == 0, (sk, n, k_block)

    def local(q_l, pos_l, k_l, v_l):
        # score-pass stream: selection is exact over ALL keys, so each
        # shard streams the full K once (same score traffic as the
        # single-device chunked pass, now split across n query shards)
        k_full = jax.lax.all_gather(k_l, ax, axis=1, tiled=True)
        thr, bm, idx, cnt = _selection_plan_local(
            q_l, k_full, pos_l, k_sel=k_sel, q_block=q_block,
            k_block=k_block, sm_scale=sm_scale, causal=causal)
        # halo exchange: the all_gather is the simulated interconnect;
        # the compact buffers (and the accounting) keep only the tiles
        # this shard's plan selects
        v_full = jax.lax.all_gather(v_l, ax, axis=1, tiled=True)
        k_tiles, tok = _gather_plan_tiles(k_full, idx, k_block=k_block)
        v_tiles, _ = _gather_plan_tiles(v_full, idx, k_block=k_block)
        out = planned_tile_attention(q_l, k_tiles, v_tiles, tok, thr,
                                     cnt, q_block=q_block,
                                     k_block=k_block, q_pos=pos_l,
                                     sm_scale=sm_scale)
        fetched = cnt.sum().reshape(1)
        return out, thr, bm, cnt, fetched

    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(None, ax, None), P(ax),
                                 P(None, ax, None), P(None, ax, None)),
                       out_specs=(P(None, ax, None), P(None, ax, None),
                                  P(None, ax, None), P(None, ax), P(ax)),
                       check_vma=False)
    q_pos = jnp.arange(s, dtype=jnp.int32)
    out, thr, bm, cnt, fetched = fn(q, q_pos, k, v)
    return out, {"thresholds": thr, "block_map": bm, "kv_counts": cnt,
                 "fetched_tiles_per_shard": fetched}


# every plan leaf keyed to where its KV-head axis sits (None = no KV
# axis → replicated).  ``live_blk`` (B, nkb) and the (B,) QoS/trigger
# vectors are slot state shared by all heads.
_PLAN_KV_AXIS: Dict[str, Optional[int]] = {
    "k_min": 1, "k_max": 1, "k_scale": 1, "k_zero": 1,
    "kv_indices": 1, "kv_counts": 1, "imp": 1,
    "live_blk": None, "step": None, "churn": None, "replans": None,
    "active": None, "budget": None, "interval": None, "quant": None,
    "sketch": None,
}


def plan_pspecs(plan: Dict, axis: str) -> Dict:
    """PartitionSpec per decode-plan leaf for KV-head tensor
    parallelism: summary bounds, plan rows and importance shard on
    their KV axis (dim 1); per-slot vectors replicate.  The result is
    shard_map in/out-spec ready — the plan pytree is a plain dict, so
    the spec dict mirrors it leaf for leaf."""
    specs = {}
    for name, val in plan.items():
        kv_dim = _PLAN_KV_AXIS[name]
        if kv_dim is None:
            specs[name] = P(*((None,) * val.ndim))
        else:
            spec = [None] * val.ndim
            spec[kv_dim] = axis
            specs[name] = P(*spec)
    return specs


def tensor_parallel_decode_step(mesh, qg, k, v, k_new, pos, plan, *,
                                topk_k: int, k_block: int,
                                replan_interval: int = 1,
                                page_table=None,
                                replan_mode: str = "exact",
                                sketch_factor: int = 4,
                                axis: Optional[str] = None):
    """One SATA decode step (summary absorb → plan update → gather
    kernel) with the plan state, KV cache and kernel sharded over KV
    heads.  Per-(slot, KV-head) independence means NO collectives: each
    shard maintains its heads' summaries, re-plans its heads' rows and
    gathers its heads' planned tiles — output and plan are bitwise the
    single-device step (``replan_interval=1`` fp32 = exact top-k).

    qg: (B, KV, G, D) grouped queries; k/v: (B, S, KV, D) contiguous
    cache or the (n_pages, page, KV, D) pool with ``page_table``
    (B, max_pages) given; k_new: (B, 1, KV, D); pos: (B,).  KV must
    tile by the mesh axis size.  The churn-adaptive trigger
    (``replan="auto"``) is per-slot-mean over *local* heads and would
    diverge across shards — integer intervals only.

    Returns ``(out (B, KV, G, D), plan')`` with ``plan'`` sharded the
    same way (pass it straight back next step).
    """
    from repro.core.decode_plan import (decode_plan_update,
                                        update_block_summaries)
    from repro.kernels.ops import sata_decode_attention
    ax = axis or mesh.axis_names[0]
    n = mesh.shape[ax]
    kv = qg.shape[1]
    assert kv % n == 0, (kv, n)
    paged = page_table is not None
    pspec = plan_pspecs(plan, ax)
    cache_spec = P(None, None, ax, None)      # KV at dim 2 both layouts

    def local(qg_l, k_l, v_l, kn_l, pos_r, plan_l, tbl):
        plan_l = update_block_summaries(plan_l, kn_l, pos_r,
                                        k_block=k_block)
        plan_l, thr = decode_plan_update(
            plan_l, qg_l, k_l, pos_r, topk_k=topk_k, k_block=k_block,
            replan_interval=replan_interval, page_table=tbl,
            replan_mode=replan_mode, sketch_factor=sketch_factor)
        out = sata_decode_attention(qg_l, k_l, v_l, plan_l["kv_indices"],
                                    plan_l["kv_counts"], thr, pos_r,
                                    k_block=k_block, page_table=tbl)
        return out, plan_l

    tbl_spec = P(None, None) if paged else P(None)
    tbl_arg = page_table if paged else jnp.zeros((1,), jnp.int32)
    if not paged:
        # shard_map needs a concrete leaf; the kernel sees None
        def local_nt(qg_l, k_l, v_l, kn_l, pos_r, plan_l, _):
            return local(qg_l, k_l, v_l, kn_l, pos_r, plan_l, None)
        body = local_nt
    else:
        body = local
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(None, ax, None, None), cache_spec,
                                 cache_spec, cache_spec, P(None), pspec,
                                 tbl_spec),
                       out_specs=(P(None, ax, None, None), pspec),
                       check_vma=False)
    return fn(qg, k, v, k_new, pos, plan, tbl_arg)
