"""Decode-path SATA benchmark → BENCH_decode.json.

The serving question: per generated token, does attention cost scale
with the *prefix* (dense decode streams every cached block) or with the
*selected* blocks (the SATA decode plan + gather kernel)?  Rows:

  * prefix sweep at a fixed selected-block budget — plan fetch-bytes
    stay flat while dense fetch grows with the prefix;
  * occupancy sweep at a long prefix — wall-clock (tok/s) vs the
    dense-schedule decode kernel (same math, all valid blocks planned),
    the decode analogue of bench_kernel's dense-vs-compacted grid;
  * exactness — with a full re-plan every step (``replan_interval=1``)
    the planned kernel is bitwise equal to the dense-schedule kernel
    (a tile whose entries are all threshold-masked is an exact no-op
    in the online softmax), and matches the pure-jnp top-k decode
    reference to fp32 accumulation tolerance;
  * plan-update cost — incremental (summary-ranked) vs full re-plan;
  * paged pool — page-table-indirect kernel vs the contiguous cache:
    bitwise parity, equal-throughput timing, and reserved-vs-used HBM
    for a mixed short/long-prefix slot mix (the utilization win paging
    exists for);
  * re-plan traffic tradeoff — amortized per-step selection bytes
    across ``sata_decode_replan`` intervals (a full re-plan streams all
    cached K; incremental steps read summaries + planned keys), the
    exactness↔traffic knob in true bytes;
  * prefill→decode handoff — a seeded plan starts decode step 0 on the
    planned incremental path (0 full re-plans) instead of cold;
  * shared-prefix page cache — N requests sharing a prompt prefix pay
    its prefill compute and HBM once (hit-rate, prefill tokens saved,
    peak-pages reduction vs private pages, CoW copies), outputs
    bitwise equal to the cache-disabled run;
  * cascade token retirement — the coldest attention blocks' pages
    freed mid-stream at a fixed pool (no-preemption completion ratio
    vs the retire-off twin), plan-side ranking-byte reduction with the
    retained-token budget, and the accuracy lane's deterministic
    divergence-vs-budget sweep;
  * mesh scaling — 2-/4-/8-way sharded selection and tensor-parallel
    decode on a simulated 8-device CPU mesh (subprocess, because
    XLA_FLAGS must precede jax init): parity vs single-device is
    bitwise-gated, per-shard fetch/work splits are exact, wall tok/s
    informational (see ``benchmarks/mesh_rows.py``).
"""
from __future__ import annotations

from typing import List

import numpy as np

from benchmarks.common import Row, timed


def _rand_plan(rng, b, kv, nkb_valid, sel, pad):
    """Per (slot, kv head): exactly ``sel`` selected blocks among the
    ``nkb_valid`` valid ones, ascending, in compact_kv_plan's padded
    layout with width ``pad``."""
    import jax.numpy as jnp
    idx = np.zeros((b, kv, pad), np.int32)
    cnt = np.full((b, kv), sel, np.int32)
    for i in range(b):
        for j in range(kv):
            pick = np.sort(rng.choice(nkb_valid, size=sel, replace=False))
            idx[i, j, :sel] = pick
            idx[i, j, sel:] = pick[-1]              # resident re-reference
    return jnp.asarray(idx), jnp.asarray(cnt)


def _jnp_topk_decode(qg, k, v, pos, topk_k):
    """Pure-jnp dense top-k (bisect) decode — the oracle the kernel's
    full-re-plan route must reproduce."""
    import jax
    import jax.numpy as jnp
    from repro.core.blockmap import bisect_select
    from repro.core.selection import NEG_INF, kth_largest_bisect
    d = qg.shape[-1]
    sc = jnp.einsum("bkgd,bskd->bkgs", qg.astype(jnp.float32),
                    k.astype(jnp.float32)) / np.sqrt(d)
    valid = (jnp.arange(k.shape[1]) <= pos[:, None])[:, None, None, :]
    sc = jnp.where(valid, sc, NEG_INF)
    thr = kth_largest_bisect(sc, topk_k)
    sel = bisect_select(jnp.where(valid, sc, -jnp.inf), thr) & valid
    sc = jnp.where(sel, sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    p = jnp.where(sel.any(-1, keepdims=True), p, 0.0)
    return jnp.einsum("bkgs,bskd->bkgd", p, v.astype(jnp.float32))


def bench_decode() -> List[Row]:
    import jax
    import jax.numpy as jnp
    from repro.core.decode_plan import full_replan
    from repro.kernels.ops import (decode_fetch_stats, default_interpret,
                                   sata_decode_attention)

    rows: List[Row] = []
    interp = default_interpret()
    mode = "interpret" if interp else "compiled"
    b, kv, g, d, blk = 2, 2, 4, 64, 128
    rng = np.random.default_rng(11)
    thr0 = jnp.zeros((b, kv, g, 1), jnp.float32)   # ~half the tile passes

    def run(s, idx, cnt, thr, pos):
        fn = jax.jit(lambda q, k_, v: sata_decode_attention(
            q, k_, v, idx, cnt, thr, pos, k_block=blk, interpret=interp))
        q = jnp.asarray(rng.standard_normal((b, kv, g, d)), jnp.float32)
        k_ = jnp.asarray(rng.standard_normal((b, s, kv, d)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((b, s, kv, d)), jnp.float32)
        jax.block_until_ready(fn(q, k_, v))                  # warm
        out, us = timed(lambda: jax.block_until_ready(fn(q, k_, v)),
                        repeat=3)
        return out, us

    # --- prefix sweep, fixed selected-block budget: plan fetch is flat
    sel_fixed = 4
    for s in (1024, 2048, 4096):
        nkb = s // blk
        pos = jnp.full((b,), s - 1, jnp.int32)
        idx, cnt = _rand_plan(rng, b, kv, nkb, sel_fixed, sel_fixed)
        _, us = run(s, idx, cnt, thr0, pos)
        st = decode_fetch_stats(cnt, pos, k_block=blk, d=d)
        rows.append((f"decode/prefix_sweep/S{s}_sel{sel_fixed}", us,
                     f"planB {st['kv_fetch_bytes_plan']} "
                     f"denseB {st['kv_fetch_bytes_dense']} "
                     f"({st['fetch_reduction']:.1f}x)"))

    # --- occupancy sweep at long prefix: tok/s vs dense-schedule kernel
    s = 4096
    nkb = s // blk
    pos = jnp.full((b,), s - 1, jnp.int32)
    idx_d = jnp.broadcast_to(jnp.arange(nkb, dtype=jnp.int32),
                             (b, kv, nkb))
    cnt_d = jnp.full((b, kv), nkb, jnp.int32)
    _, us_dense = run(s, idx_d, cnt_d, thr0, pos)
    tok_dense = b * 1e6 / us_dense
    rows.append((f"decode/dense_{mode}/S{s}", us_dense,
                 f"{tok_dense:.1f} tok/s, fetch tiles {b * kv * nkb}"))
    for occ in (0.25, 0.5):
        sel = max(1, int(occ * nkb))
        idx, cnt = _rand_plan(rng, b, kv, nkb, sel, sel)
        _, us_sata = run(s, idx, cnt, thr0, pos)
        st = decode_fetch_stats(cnt, pos, k_block=blk, d=d)
        tok = b * 1e6 / us_sata
        rows.append((f"decode/sata_{mode}/S{s}_occ{occ:.2f}", us_sata,
                     f"{tok:.1f} tok/s, fetch tiles "
                     f"{st['kv_fetch_tiles_plan']}"))
        rows.append((f"decode/speedup/S{s}_occ{occ:.2f}", 0.0,
                     f"{us_dense / max(us_sata, 1e-9):.2f}x tok/s "
                     f"({mode}), {st['fetch_reduction']:.2f}x fetch-bytes"))

    # --- exactness at replan_interval=1: planner plan vs dense schedule
    s = 1024
    nkb = s // blk
    pos = jnp.full((b,), s - 1, jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, kv, g, d)), jnp.float32)
    k_ = jnp.asarray(rng.standard_normal((b, s, kv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, kv, d)), jnp.float32)
    topk_k = 64
    idx_p, cnt_p, thr = jax.jit(
        lambda q, k__: full_replan(q, k__, pos, topk_k=topk_k, k_block=blk,
                                   plan_blocks=nkb))(q, k_)
    out_plan = sata_decode_attention(q, k_, v, idx_p, cnt_p, thr, pos,
                                     k_block=blk, interpret=interp)
    idx_d = jnp.broadcast_to(jnp.arange(nkb, dtype=jnp.int32), (b, kv, nkb))
    cnt_d = jnp.full((b, kv), nkb, jnp.int32)
    out_dense = sata_decode_attention(q, k_, v, idx_d, cnt_d, thr, pos,
                                      k_block=blk, interpret=interp)
    err = float(jnp.max(jnp.abs(out_plan - out_dense)))
    occ_plan = float(cnt_p.sum()) / (b * kv * nkb)
    rows.append((f"decode/parity_replan1/S{s}", 0.0,
                 f"max_err {err:.2e} vs dense schedule at "
                 f"{occ_plan:.2f} occupancy"))
    ref = _jnp_topk_decode(q, k_, v, pos, topk_k)
    err_ref = float(jnp.max(jnp.abs(out_plan.astype(jnp.float32) - ref)))
    rows.append((f"decode/parity_vs_jnp/S{s}", 0.0,
                 f"max_err {err_ref:.2e} (fp32 accumulation-order tol)"))

    # --- plan maintenance cost: full re-plan vs incremental update
    from repro.core.decode_plan import (decode_plan_update,
                                        init_decode_plan,
                                        summaries_from_cache)
    plan = init_decode_plan(b, kv, s, d, blk, plan_blocks=nkb // 4)
    k_min, k_max = summaries_from_cache(k_, pos, k_block=blk)
    plan = {**plan, "k_min": k_min, "k_max": k_max,
            "step": jnp.ones((b,), jnp.int32)}      # off the replan beat
    for name, interval in (("full", 1), ("incremental", 1 << 30)):
        fn = jax.jit(lambda p, q_, k__, iv=interval: decode_plan_update(
            p, q_, k__, pos, topk_k=topk_k, k_block=blk,
            replan_interval=iv))
        jax.block_until_ready(fn(plan, q, k_))
        _, us = timed(lambda: jax.block_until_ready(fn(plan, q, k_)),
                      repeat=3)
        rows.append((f"decode/plan_update_{name}/S{s}", us,
                     f"P {nkb // 4} of nkb {nkb}"))

    rows += _bench_paged(rng, interp, mode)
    rows += _bench_replan_traffic()
    rows += _bench_handoff()
    rows += _bench_shared_prefix()
    rows += _bench_fault_swap()
    rows += _bench_degradation()
    rows += _bench_retirement()
    rows += _bench_mesh()
    return rows


def _bench_mesh() -> List[Row]:
    """2-/4-/8-way mesh scaling rows via ``benchmarks.mesh_rows`` in a
    subprocess — the forced host device count must be set before jax
    initializes, and this process's jax is already up single-device."""
    import json
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=8").strip()
    # the simulated mesh is CPU devices; never let the child reach for
    # an accelerator this process may already hold
    env["JAX_PLATFORMS"] = "cpu"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        [root, os.path.join(root, "src"),
         env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    proc = subprocess.run([sys.executable, "-m", "benchmarks.mesh_rows"],
                          capture_output=True, text=True, env=env,
                          cwd=root, timeout=1200)
    if proc.returncode != 0:
        raise RuntimeError(f"mesh_rows subprocess failed:\n{proc.stderr}")
    for line in proc.stdout.splitlines():
        if line.startswith("MESH_ROWS_JSON:"):
            return [tuple(r) for r in
                    json.loads(line[len("MESH_ROWS_JSON:"):])]
    raise RuntimeError(f"mesh_rows emitted no row marker:\n{proc.stdout}")


def _bench_paged(rng, interp, mode) -> List[Row]:
    """Paged pool vs contiguous cache: bitwise parity, equal-throughput
    kernel timing, and reserved-vs-used HBM at a mixed short/long-prefix
    slot mix — the serving-utilization case paging exists for."""
    import jax
    import jax.numpy as jnp
    from repro.core.decode_plan import full_replan
    from repro.core.paging import PageAllocator, logical_kv_view
    from repro.kernels.ops import sata_decode_attention

    rows: List[Row] = []
    b, kv, g, d, blk = 4, 2, 4, 64, 128
    s = 4096
    nkb = s // blk
    # mixed slot mix: one max_len prefix, three short ones — contiguous
    # reserves B·max_len regardless; the pool holds only mapped pages
    pos = jnp.asarray([s - 1, 511, 255, 127], jnp.int32)
    used_pages = int(sum(int(p) // blk + 1 for p in pos))
    n_pages = used_pages + used_pages // 4 + 1      # 25% headroom + ovf
    alloc = PageAllocator(n_pages, b, nkb, blk)
    for i in range(b):
        ok = alloc.ensure(i, int(pos[i]))
        assert ok, (i, int(pos[i]))
    tbl = jnp.asarray(alloc.table)

    k_c = jnp.asarray(rng.standard_normal((b, s, kv, d)), jnp.float32)
    v_c = jnp.asarray(rng.standard_normal((b, s, kv, d)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((b, kv, g, d)), jnp.float32)
    # scatter the SAME rows into the pool so both layouts see one cache
    k_p = jnp.zeros((n_pages, blk, kv, d), jnp.float32)
    v_p = jnp.zeros((n_pages, blk, kv, d), jnp.float32)
    for i in range(b):
        for lp in range(int(pos[i]) // blk + 1):
            ph = int(alloc.table[i, lp])
            k_p = k_p.at[ph].set(k_c[i, lp * blk:(lp + 1) * blk])
            v_p = v_p.at[ph].set(v_c[i, lp * blk:(lp + 1) * blk])
    assert bool((logical_kv_view(k_p, tbl) * (
        jnp.arange(s)[None, :, None, None] <= pos[:, None, None, None])
        == k_c * (jnp.arange(s)[None, :, None, None]
                  <= pos[:, None, None, None])).all())

    idx, cnt, thr = jax.jit(lambda q_, k__: full_replan(
        q_, k__, pos, topk_k=64, k_block=blk, plan_blocks=nkb))(q, k_c)

    fn_c = jax.jit(lambda q_, k__, v__: sata_decode_attention(
        q_, k__, v__, idx, cnt, thr, pos, k_block=blk, interpret=interp))
    fn_p = jax.jit(lambda q_, k__, v__: sata_decode_attention(
        q_, k__, v__, idx, cnt, thr, pos, k_block=blk, page_table=tbl,
        interpret=interp))
    out_c = fn_c(q, k_c, v_c)
    out_p = fn_p(q, k_p, v_p)
    err = float(jnp.max(jnp.abs(out_c - out_p)))
    rows.append((f"decode/paged_parity/S{s}_mixed", 0.0,
                 f"max_err {err:.2e} paged vs contiguous (replan=1 plan)"))
    jax.block_until_ready(fn_c(q, k_c, v_c))
    _, us_c = timed(lambda: jax.block_until_ready(fn_c(q, k_c, v_c)),
                    repeat=3)
    jax.block_until_ready(fn_p(q, k_p, v_p))
    _, us_p = timed(lambda: jax.block_until_ready(fn_p(q, k_p, v_p)),
                    repeat=3)
    row_bytes = 2 * kv * d * 4
    reserved_c = b * s * row_bytes
    reserved_p = n_pages * blk * row_bytes
    used_p = used_pages * blk * row_bytes
    rows.append((f"decode/paged_tok_s_{mode}/S{s}_mixed", us_p,
                 f"{b * 1e6 / us_p:.1f} tok/s paged vs "
                 f"{b * 1e6 / us_c:.1f} contiguous "
                 f"({us_c / max(us_p, 1e-9):.2f}x)"))
    rows.append((f"decode/paged_hbm/S{s}_mixed", 0.0,
                 f"reserved {reserved_p} B vs {reserved_c} B contiguous "
                 f"({reserved_c / reserved_p:.2f}x less), used {used_p} B "
                 f"({used_p / reserved_p:.2f} pool occupancy)"))
    return rows


def _bench_replan_traffic() -> List[Row]:
    """Amortized per-step selection+kernel bytes across re-plan
    intervals: interval 1 is exact but streams all cached K every step;
    longer intervals amortize the full re-plan over cheap incremental
    steps (summaries + planned keys).  The backend × mode rows price
    the summary-traffic knobs: int8 summaries shrink every ranking
    read ~4x, and the sketch re-plan replaces the all-cached-K stream
    with summaries + C·F candidate blocks — selection traffic
    sub-linear in cached K even at interval 1."""
    import numpy as np
    from repro.kernels.ops import decode_fetch_stats

    rows: List[Row] = []
    b, kv, d, blk, s = 2, 2, 64, 128, 4096
    nkb = s // blk
    sel = nkb // 4                                 # 25% occupancy plan
    cnt = np.full((b, kv), sel)
    pos = np.full(b, s - 1)
    for interval in (1, 2, 4, 16):
        st = decode_fetch_stats(cnt, pos, k_block=blk, d=d,
                                replan=1.0 / interval, nkb=nkb)
        tag = "exact" if interval == 1 else "approx"
        rows.append((f"decode/replan_traffic/S{s}_iv{interval}", 0.0,
                     f"step {st['step_bytes_plan_route']} B plan-route vs "
                     f"{st['step_bytes_dense_route']} B dense ("
                     f"{st['step_bytes_dense_route'] / st['step_bytes_plan_route']:.2f}x, "
                     f"plan side {st['plan_fetch_bytes_step']} B, {tag})"))
    # summary backend × re-plan mode (fp32+exact above is the baseline)
    plan_side = {}
    for summary, rmode in (("int8", "exact"), ("fp32", "sketch"),
                           ("int8", "sketch")):
        for interval in (1, 4):
            st = decode_fetch_stats(cnt, pos, k_block=blk, d=d,
                                    replan=1.0 / interval, nkb=nkb,
                                    summary=summary, replan_mode=rmode,
                                    sketch_factor=4, plan_blocks=sel)
            plan_side[(summary, rmode, interval)] = \
                st["plan_fetch_bytes_step"]
            rows.append((
                f"decode/replan_traffic/S{s}_iv{interval}_{summary}_{rmode}",
                0.0,
                f"step {st['step_bytes_plan_route']} B plan-route vs "
                f"{st['step_bytes_dense_route']} B dense ("
                f"{st['step_bytes_dense_route'] / st['step_bytes_plan_route']:.2f}x, "
                f"plan side {st['plan_fetch_bytes_step']} B, "
                f"{summary}+{rmode})"))
    fp_exact = decode_fetch_stats(cnt, pos, k_block=blk, d=d, replan=1.0,
                                  nkb=nkb)["plan_fetch_bytes_step"]
    i8_sk = plan_side[("int8", "sketch", 1)]
    rows.append((f"decode/replan_traffic/S{s}_reduction", 0.0,
                 f"int8+sketch plan-side {i8_sk} B vs {fp_exact} B "
                 f"fp32-exact at iv1 "
                 f"({fp_exact / i8_sk:.2f}x selection-traffic reduction)"))
    return rows


def _bench_handoff() -> List[Row]:
    """Prefill→decode handoff on the reduced serving model: a seeded
    plan runs decode step 0 on the planned incremental path (0 full
    re-plans), where a cold claim would re-plan (stream the whole
    prefix) first."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs.archs import SMOKE
    from repro.models import decode as dec
    from repro.models import model as mdl

    cfg = dataclasses.replace(SMOKE["qwen3-4b"], topk_impl="bisect",
                              sata_decode="on", sata_decode_block=8,
                              sata_decode_replan=8)
    params = mdl.init_params(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (1, 8)), jnp.int32)
    max_len = 32

    lg0, state = dec.prefill_prompt(params, cfg, toks, max_len)
    cache = dec.init_cache(cfg, 1, max_len)
    cache = dec.install_prefill(cfg, cache, 0, state)
    nxt = jnp.argmax(lg0, -1)[:, None].astype(jnp.int32)
    _, cache = dec.serve_step(params, cfg, cache, nxt, jnp.int32(8))
    seeded = int(np.asarray(cache["kv"]["plan"]["replans"])[0, 0])
    planned = int(np.asarray(cache["kv"]["plan"]["kv_counts"]).min())

    cold = dec.init_cache(cfg, 1, max_len)
    for t in range(8):
        _, cold = dec.serve_step(params, cfg, cold, toks[:, t:t + 1],
                                 jnp.int32(t))
    _, cold = dec.serve_step(params, cfg, cold, nxt, jnp.int32(8))
    cold_replans = int(np.asarray(cold["kv"]["plan"]["replans"])[0, 0])
    return [("decode/prefill_handoff/step0", 0.0,
             f"seeded: {seeded} full re-plans at decode step 0 "
             f"(plan rows live, min counts {planned}) vs {cold_replans} "
             f"on the cold token-by-token path")]


def _bench_shared_prefix() -> List[Row]:
    """Shared-prefix page cache on the reduced serving model: six
    requests share a 16-token prefix of their 20-token prompts.  With
    the cache, the shared pages prefill once and later claims map them
    (refcount bump); the rows report prefill-compute and peak-HBM
    reduction vs the private-pages (cache-off) twin, plus the
    output-equality flag the regression gate pins exactly."""
    import dataclasses
    import time

    from repro.configs.archs import SMOKE
    from repro.launch.serve import serve

    cfg = dataclasses.replace(
        SMOKE["qwen3-4b"], topk_impl="bisect", sata_decode="on",
        sata_decode_block=8, sata_decode_replan=1,
        kv_cache_layout="paged")
    kw = dict(smoke=True, n_requests=6, batch_slots=3, gen_len=8,
              max_len=64, prompt_len=20, shared_prefix_len=16)
    t0 = time.perf_counter()
    off = serve("qwen3-4b", cfg=cfg, **kw)
    us_off = (time.perf_counter() - t0) * 1e6
    t0 = time.perf_counter()
    on = serve("qwen3-4b",
               cfg=dataclasses.replace(cfg, kv_prefix_cache=True), **kw)
    us_on = (time.perf_counter() - t0) * 1e6
    p = on["prefix_cache"]
    eq = on["outputs"] == off["outputs"]
    total = p["prefill_tokens_total"]
    saved = p["prefill_tokens_saved"]

    # HBM story: private pages demand peak_off pages; sharing fits the
    # SAME workload in a pool smaller than that demand without any
    # backpressure, because concurrent slots alias the prefix pages
    peak_off = off["page_occupancy"]["pages_in_use_peak"]
    page_b = off["page_occupancy"]["hbm_reserved_bytes"] \
        // off["page_occupancy"]["n_pages"]
    tight = dataclasses.replace(cfg, kv_prefix_cache=True,
                                kv_pool_pages=peak_off - 1)
    on_t = serve("qwen3-4b", cfg=tight, **kw)
    off_t = serve("qwen3-4b",
                  cfg=dataclasses.replace(tight, kv_prefix_cache=False),
                  **kw)
    occ_on, occ_off = on_t["page_occupancy"], off_t["page_occupancy"]
    bp_on = occ_on["stalled_steps"] + occ_on["deferred_claims"] \
        + occ_on["preemptions"]
    bp_off = occ_off["stalled_steps"] + occ_off["deferred_claims"] \
        + occ_off["preemptions"]
    eq_t = on_t["outputs"] == off["outputs"]
    # all rows derived-only (us 0.0): serve wall on CPU is dominated by
    # per-shape jit compiles — fine as trajectory text, too noisy for
    # the regression gate's wall band
    return [
        ("decode/shared_prefix/prefill", 0.0,
         f"saved {saved}/{total} prefill tokens "
         f"({p['hits']}/{p['requests']} hits), "
         f"{total / max(total - saved, 1):.2f}x prefill-compute "
         f"reduction, {p['cow_copies']} CoW copies, shared-page peak "
         f"{p['shared_pages_peak']}, outputs_equal={eq}"),
        ("decode/shared_prefix/hbm", 0.0,
         f"reserved {(peak_off - 1) * page_b} B pool serves the "
         f"workload private pages demand {peak_off * page_b} B for: "
         f"backpressure {bp_on} shared vs {bp_off} private, "
         f"outputs_equal={eq_t}"),
        ("decode/shared_prefix/serve_wall", 0.0,
         f"cache-on {us_on:.0f}us vs cache-off {us_off:.0f}us serve "
         f"wall (jit-inclusive, informational)"),
    ]


def _bench_fault_swap() -> List[Row]:
    """Preemption policy on the reduced serving model: a deterministic
    pool squeeze forces preemptions, served once with host-swap (pages
    + plan state round-trip through host memory, zero re-prefill) and
    once with the legacy requeue fallback (host budget = 0: outputs
    discarded, prompt re-prefilled).  Both must stay bitwise equal to
    the fault-free run — the gate pins the salvage/discard counters and
    equality flags exactly; restore wall is informational."""
    import dataclasses

    from repro.configs.archs import SMOKE
    from repro.launch.faults import FaultPlan
    from repro.launch.serve import serve

    cfg = dataclasses.replace(
        SMOKE["qwen3-4b"], topk_impl="bisect", sata_decode="on",
        sata_decode_block=8, sata_decode_replan=4,
        kv_cache_layout="paged", kv_pool_pages=6)
    kw = dict(smoke=True, n_requests=4, batch_slots=2, gen_len=12,
              max_len=32, prompt_len=6)
    base = serve("qwen3-4b", cfg=cfg, **kw)
    faults = FaultPlan().pool_squeeze(2, 3).pool_restore(14)
    swap = serve("qwen3-4b", cfg=cfg, faults=faults, **kw)
    requeue = serve("qwen3-4b", cfg=cfg, faults=faults,
                    host_swap_bytes=0, **kw)
    s, r = swap["page_occupancy"], requeue["page_occupancy"]
    eq_s = swap["outputs"] == base["outputs"]
    eq_r = requeue["outputs"] == base["outputs"]
    restore_us = s["swap_restore_wall_s"] * 1e6 \
        / max(s["swap_restores"], 1)
    return [
        ("decode/fault_swap/salvage", 0.0,
         f"{s['tokens_salvaged']} tokens salvaged over "
         f"{s['host_swaps']} host-swaps ({s['swap_restores']} restores, "
         f"re_prefill_tokens={s['re_prefill_tokens']}, "
         f"cold_replans={s['swap_cold_replans']}), "
         f"outputs_equal={eq_s}"),
        ("decode/fault_swap/requeue_baseline", 0.0,
         f"requeue discarded {r['requeue_tokens_discarded']} tokens "
         f"over {r['requeue_preemptions']} preemptions, "
         f"re_prefill_tokens={r['re_prefill_tokens']}, "
         f"outputs_equal={eq_r}"),
        ("decode/fault_swap/restore_latency", 0.0,
         f"swap-in restore {restore_us:.0f}us/restore mean, host-swap "
         f"peak {s['host_swap_bytes_peak']} B "
         f"(jit-inclusive, informational)"),
    ]


def _bench_retirement() -> List[Row]:
    """Cascade token retirement on the reduced serving model, two
    lanes.  Pressure lane: a mixed-prefix workload (six 60-token
    requests sharing a 12-token prefix) against a 16-page pool that
    holds barely two full prefixes — retire-off sheds by preemption;
    retire-on frees the coldest blocks' pages mid-stream, and the gate
    pins reclaimed pages, the no-preemption completion ratio (must
    stay >= 1.5x), and the plan-side ranking-byte reduction exactly.
    Accuracy lane (ample pool, so every difference is retirement's):
    deterministic token-divergence vs the retire-off twin across
    retained-token budgets — retirement is lossy BY DESIGN and the
    trajectory must price that, not hide it.  A watermark no slot can
    reach must reproduce retire-off bitwise (the off-path contract)."""
    import dataclasses

    from repro.configs.archs import SMOKE
    from repro.launch.serve import serve

    cfg = dataclasses.replace(
        SMOKE["qwen3-4b"], topk_impl="bisect", sata_decode="on",
        sata_decode_block=8, sata_decode_replan=1,
        kv_cache_layout="paged")
    kw = dict(smoke=True, n_requests=6, batch_slots=3, gen_len=40,
              max_len=64, prompt_len=20, shared_prefix_len=12)
    n = kw["n_requests"]

    def ret(keep, pool, watermark=0.4):
        return serve("qwen3-4b", cfg=dataclasses.replace(
            cfg, kv_pool_pages=pool, sata_retire="on",
            sata_retire_watermark=watermark, sata_retire_keep=keep), **kw)

    # --- pressure lane: fixed 16-page pool
    off_p = serve("qwen3-4b",
                  cfg=dataclasses.replace(cfg, kv_pool_pages=16), **kw)
    on_p = ret(0.5, 16)
    r = on_p["retirement"]
    first_ev = min((t[0][0] for t in r["timelines"].values() if t),
                   default=on_p["steps"])
    oo, op = on_p["page_occupancy"], off_p["page_occupancy"]
    ok_on = n - oo["preempted_requests"]
    ok_off = n - op["preempted_requests"]
    ratio = ok_on / max(ok_off, 1)

    # --- accuracy + traffic lane: ample pool, retirement is the only
    # difference; divergence = token mismatch rate vs the off twin
    off_a = serve("qwen3-4b", cfg=cfg, **kw)
    total = sum(len(v) for v in off_a["outputs"].values())

    def diverge(on):
        d = sum(1 for req, toks in off_a["outputs"].items()
                for j, t in enumerate(toks)
                if on["outputs"][req][j] != t)
        return d / max(total, 1)

    sweep = {keep: ret(keep, 0) for keep in (0.75, 0.5, 0.25)}
    b_off = off_a["decode_fetch"]["plan_fetch_bytes"]
    b50 = sweep[0.5]["decode_fetch"]["plan_fetch_bytes"]
    b25 = sweep[0.25]["decode_fetch"]["plan_fetch_bytes"]
    never = ret(0.5, 0, watermark=2.0)         # can never fire
    eq_never = never["outputs"] == off_a["outputs"]
    return [
        ("decode/retirement/reclaim", 0.0,
         f"reclaimed {r['pages_reclaimed']} pages over {r['events']} "
         f"events ({r['retired_tokens']} tokens retired, keep 0.50, "
         f"16-page pool), first at step {first_ev}/{on_p['steps']} "
         f"(mid-stream)"),
        ("decode/retirement/completion", 0.0,
         f"no-preemption completions {ok_on}/{n} retire-on vs "
         f"{ok_off}/{n} retire-off ({ratio:.2f}x), preemptions "
         f"{oo['preemptions']} vs {op['preemptions']}, stalled steps "
         f"{oo['stalled_steps']} vs {op['stalled_steps']}"),
        ("decode/retirement/plan_bytes", 0.0,
         f"plan-side ranking traffic {b50} B at keep 0.50, {b25} B at "
         f"keep 0.25 vs {b_off} B retire-off "
         f"({b_off / max(b50, 1):.2f}x/{b_off / max(b25, 1):.2f}x "
         f"reduction with the retained-token budget)"),
        ("decode/retirement/accuracy", 0.0,
         f"token divergence vs retained-token budget: keep 0.75 -> "
         f"{diverge(sweep[0.75]):.4f}, 0.50 -> "
         f"{diverge(sweep[0.5]):.4f}, 0.25 -> "
         f"{diverge(sweep[0.25]):.4f} (mismatch rate vs retire-off; "
         f"lossy by design, priced not hidden)"),
        ("decode/retirement/off_bitwise", 0.0,
         f"unreachable watermark: outputs_equal={eq_never} to "
         f"retire-off with {never['retirement']['pages_reclaimed']} "
         f"pages reclaimed"),
    ]


def _bench_degradation() -> List[Row]:
    """Overload policy on the reduced serving model: a deterministic
    load-spike + slow-step schedule served once with the SLO
    degradation ladder (per-slot plan-quality rungs absorb the
    pressure: every request completes, zero requeues/timeouts) and
    once without it (the PR 7 behavior: the spike sheds requests by
    preemption/requeue).  A second schedule parks a swap handle and
    corrupts one payload byte — the swap-in checksum gate must detect
    and quarantine it, with the victim recovering by re-prefill.  The
    gate pins every counter exactly; there are no wall rows."""
    import dataclasses

    from repro.configs.archs import SMOKE
    from repro.launch.faults import FaultPlan
    from repro.launch.serve import serve

    cfg = dataclasses.replace(
        SMOKE["qwen3-4b"], topk_impl="bisect", sata_decode="on",
        sata_decode_block=8, sata_decode_replan=4,
        kv_cache_layout="paged", kv_pool_pages=6, sata_qos_ladder=True)
    cfg_off = dataclasses.replace(cfg, sata_qos_ladder=False)
    kw = dict(smoke=True, n_requests=4, batch_slots=2, gen_len=12,
              max_len=32, prompt_len=6)
    spikes = FaultPlan().load_spike(4, 2).slow_step(5).load_spike(10, 1)
    base = serve("qwen3-4b", cfg=cfg, **kw)
    lad = serve("qwen3-4b", cfg=cfg, faults=spikes, **kw)
    req = serve("qwen3-4b", cfg=cfg_off, faults=spikes, **kw)
    lo, ro, q = lad["page_occupancy"], req["page_occupancy"], lad["qos"]
    # requests the ladder never degraded must be bitwise equal to the
    # no-fault run (per-slot knob isolation)
    eq_undeg = all(lad["outputs"][r] == base["outputs"][r]
                   for r, tl in lad["degradation"].items() if not tl)
    corr = (FaultPlan().preempt(6).defer_admission(6).defer_admission(7)
            .corrupt_page(7).defer_admission(8))
    intg = serve("qwen3-4b", cfg=cfg, faults=corr, **kw)
    io = intg["page_occupancy"]
    eq_intg = intg["outputs"] == base["outputs"]
    return [
        ("decode/degradation/ladder", 0.0,
         f"completed {len(lad['request_latency_s'])}/{kw['n_requests']} "
         f"requests under spike, requeues={lo['requeue_preemptions']}, "
         f"timeouts={len(lad['timed_out'])}, "
         f"degraded_steps={q['degraded_steps']}, "
         f"rung_downs={q['rung_downs']}, rung_ups={q['rung_ups']}, "
         f"outputs_equal={eq_undeg}"),
        ("decode/degradation/requeue_baseline", 0.0,
         f"completed {len(req['request_latency_s'])}/{kw['n_requests']} "
         f"requests under spike, requeue discarded "
         f"{ro['requeue_tokens_discarded']} tokens over "
         f"{ro['preemptions']} preemptions, "
         f"re_prefill_tokens={ro['re_prefill_tokens']}"),
        ("decode/degradation/integrity", 0.0,
         f"corrupt_injected={io['corrupt_pages_injected']}, "
         f"corrupt_detected={io['corrupt_pages_detected']}, "
         f"quarantined_pages={io['quarantined_pages']}, "
         f"re_prefill_tokens={io['re_prefill_tokens']}, "
         f"outputs_equal={eq_intg}"),
    ]
