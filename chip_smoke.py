#!/usr/bin/env python3
"""On-chip smoke test: serve full-width qwen3-4b on one TPU, with the
SATA decode route compiled, and check what comes out.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: the mesh paths only

One chip, all in this one process (a second process could not reach the
chip this one holds):

1. kernel parity — the compiled decode gather kernels (contiguous and
   paged) and the threshold-mode causal prefill kernel at qwen3-4b
   attention widths, each against a float32 ``jnp`` reference under
   ``jax.default_matmul_precision("highest")`` whose key selection and
   plan are made on the host (``ml_dtypes`` rounding, plain numpy), so
   a fault in the program's own selection predicate cannot hide in both;
2. serve — 8 requests through ``serve()`` at the published qwen3-4b
   widths (weights random from ``--seed``): 4 slots, ``max_len`` 2048,
   1024-token prompts, 32 generated tokens, paged KV pool, SATA decode
   forced on, block = page = 128.  Every request must complete and the
   report must carry the SATA ``decode_fetch`` accounting;
3. route parity — the first decode step's logits through the SATA
   route (plan + gather kernel, which must be in the compiled step)
   against the dense route (``sata.decode.mode="off"``) from the same
   prefill; both finite.

Four chips: ``tensor_parallel_decode_step`` over KV heads (8 heads, 2
per chip) and ``sequence_sharded_attention`` 4-way, each beside its
single-device twin.

Every check that fails exits non-zero before the last line.  The last
line of standard output is one JSON object naming the device as JAX
reports it.  The script refuses to run without a TPU, and without the
repository's ``src/`` next to it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
sys.path.insert(0, SRC)

# qwen3-4b attention widths (configs/archs.py): 8 KV heads, 4 query
# heads per KV head, head_dim 128; a 2048-token cache in 128-token blocks
B, KV, G, D, S, BLK, TOPK = 4, 8, 4, 128, 2048, 128, 64

# Kernel-vs-reference tolerance, per output element, as a fraction of
# ``mag + |ref|`` where ``mag = sum_i p_i·|v_i|`` (softmax weights p).
# The inputs make the scores bitwise equal on both sides (see
# ``_grid_values``), so both pick the same keys; what is left is two
# bf16 roundings in the kernel (unit roundoff 2^-8): the softmax weights
# enter the P·V matmul as bf16 (error <= 2^-8·mag) and the output is
# stored as bf16 (error <= 2^-8·|out|).  The bound allows twice their
# sum.  A key selected on one side only moves an element by about
# p_i·|v_i|, outside what the two roundings explain.
KERNEL_TOL = 2.0 ** -7

# SATA-vs-dense route tolerance on the first decode step's logits,
# relative: max|diff| / max|logits|.  Both routes share the prefill
# bit for bit and select the same keys; at the decode position each
# layer's attention differs by rounding only (the kernel's online
# softmax feeds bf16 weights to P·V), and 36 bf16 layers carry that to
# the logits.  How far rounding alone moves these logits on a v5e was
# measured at these widths and seed: the dense decode step and a dense
# prefill of the same 1025 tokens (the same math) differ by 0.041, and
# the SATA route sits 0.042 from the dense one.  Broken on purpose, the
# SATA route moved by 0.52 with the first planned block of every head
# dropped and by 0.48 with the page table read one page off.  0.15 is
# over three times the rounding and about a third of the smallest
# breakage.
ROUTE_TOL = 0.15

# Mesh-vs-single-device tolerance, as a fraction of max|v|.  Each shard
# runs the computation the single device runs for its heads (tensor
# parallel) or its query rows (sequence sharded), so on the CPU the two
# agree bitwise; on the chip the compiler may order a shard's reductions
# differently.  2^-7·max|v| is a few bf16 roundings of the output, far
# below what a wrong head, a misrouted shard or a dropped block does.
TWIN_TOL = 2.0 ** -7


def fail(msg: str) -> None:
    print(f"[chip_smoke] FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(cond, msg: str) -> None:
    if not cond:
        fail(msg)


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class Clock:
    """Sums the seconds spent in explicit ahead-of-time compiles."""

    def __init__(self):
        self.compile_s = 0.0

    def compile(self, fn, *args):
        import jax
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(*args).compile()
        self.compile_s += time.perf_counter() - t0
        return compiled


def _grid_values(rng, shape):
    """Normal draws rounded to multiples of 1/8 in [-4, 4]: exact in
    bf16, and every partial sum of a length-128 dot of two such vectors
    is a multiple of 1/64 below 2^11, so the dot is exact in fp32 in
    any order.  Kernel and reference then compute the same scores bit
    for bit and select the same keys."""
    import numpy as np
    return np.clip(np.round(rng.standard_normal(shape) * 8) / 8, -4, 4)


# ---------------------------------------------------------------------------
# phase 1: kernel parity
# ---------------------------------------------------------------------------

def _ref_attention(s, sel, v, vspec):
    """fp32 selective softmax attention over selected scores.  Returns
    (output, mag) with ``mag = sum_i p_i·|v_i|``, the scale of the
    kernel's rounding error (see ``KERNEL_TOL``)."""
    import jax
    import jax.numpy as jnp
    s = jnp.where(sel, s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(sel, jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0)), 0.0)
    p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    v = v.astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        return jnp.einsum(vspec, p, v), jnp.einsum(vspec, p, jnp.abs(v))


def _errors(out, ref, mag):
    """(max abs error, max error in units of ``mag + |ref|``)."""
    import jax.numpy as jnp
    err = jnp.abs(out.astype(jnp.float32) - ref)
    scale = mag + jnp.abs(ref)
    rel = jnp.where(scale > 0, err / jnp.where(scale > 0, scale, 1.0),
                    jnp.where(err > 0, jnp.inf, 0.0))
    return float(jnp.max(err)), float(jnp.max(rel))


def _topk_threshold(s, adm, topk):
    """Per-row k-th largest admissible score (-1e30 when a row has
    fewer than ``topk`` admissible keys: everything admissible)."""
    import jax
    import jax.numpy as jnp
    kth = jax.lax.top_k(jnp.where(adm, s, -jnp.inf), topk)[0][..., -1:]
    return jnp.where(jnp.isfinite(kth), kth, -1e30)


def _host_select(sc, thr, adm):
    """The selection predicate, made on the host apart from the
    program's own (``core.blockmap.bisect_select``): scores and
    thresholds rounded to bf16 by ``ml_dtypes`` (nearest, ties to even),
    compared in fp32, gated by admissibility."""
    import ml_dtypes
    import numpy as np

    def bf16(x):
        return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
            np.float32)

    return (bf16(sc) >= bf16(thr)) & adm


def _host_plan(bm):
    """Compact plan of a block map in plain numpy: each row's occupied
    block indices in order, padded by repeating the last one, and the
    count per row."""
    import numpy as np
    cnt = bm.sum(-1).astype(np.int32)
    order = np.argsort(~bm, axis=-1, kind="stable").astype(np.int32)
    last = np.take_along_axis(order, np.maximum(cnt - 1, 0)[..., None], -1)
    idx = np.where(np.arange(bm.shape[-1]) < cnt[..., None], order, last)
    return idx.astype(np.int32), cnt


def decode_kernel_parity(clock, *, b, kv, g, d, s, blk, topk, interpret,
                         seed=0):
    """Contiguous and paged decode gather kernels vs an fp32 reference
    whose selection and plan are made on the host.  Returns ``_errors``
    of each, and whether the two agree bitwise."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.sata_decode import (
        sata_decode_attention_kernel, sata_decode_attention_paged_kernel)

    rng = np.random.default_rng(seed)
    bf = jnp.bfloat16
    q = jnp.asarray(_grid_values(rng, (b, kv, g, d)), bf)
    k = jnp.asarray(_grid_values(rng, (b, s, kv, d)), bf)
    v = jnp.asarray(rng.standard_normal((b, s, kv, d)), bf)
    # ragged slot positions, the last slot on a block edge
    pos_h = np.linspace(s - 1, blk - 1, b).astype(np.int32)
    pos = jnp.asarray(pos_h)
    scale = 1.0 / np.sqrt(d)
    nkb = s // blk
    adm = np.arange(s)[None, None, None, :] <= pos_h[:, None, None, None]

    def scores(q, k):
        with jax.default_matmul_precision("highest"):
            raw = jnp.einsum("bkgd,bskd->bkgs", q.astype(jnp.float32),
                             k.astype(jnp.float32))
        # the barrier keeps XLA from folding the scale into q: the
        # kernel scales after its dot, and so must the reference
        sc = jax.lax.optimization_barrier(raw) * scale
        return sc, _topk_threshold(sc, jnp.asarray(adm), topk)

    sc, thr = clock.compile(scores, q, k)(q, k)
    sel = _host_select(sc, thr, adm)
    idx, cnt = (jnp.asarray(a) for a in _host_plan(
        sel.reshape(b, kv, g, nkb, blk).any(axis=(2, 4))))
    sel = jnp.asarray(sel)
    out_ref, mag = clock.compile(
        lambda sc, sel, v: _ref_attention(sc, sel, v, "bkgs,bskd->bkgd"),
        sc, sel, v)(sc, sel, v)
    del sc, sel

    def contiguous(q, k, v, idx, cnt, thr, pos):
        return sata_decode_attention_kernel(q, k, v, idx, cnt, thr, pos,
                                            k_block=blk, interpret=interpret)

    out_c = clock.compile(contiguous, q, k, v, idx, cnt, thr, pos)(
        q, k, v, idx, cnt, thr, pos)

    # the same cache scattered over a shuffled page pool (page 0 is the
    # overflow page, never mapped)
    n_pages = b * nkb + 1
    tbl = jnp.asarray((rng.permutation(b * nkb) + 1).reshape(b, nkb),
                      jnp.int32)

    def to_pages(x):
        return jnp.zeros((n_pages, blk, kv, d), x.dtype).at[
            tbl.reshape(-1)].set(x.reshape(b * nkb, blk, kv, d))

    kp, vp = to_pages(k), to_pages(v)

    def paged(q, kp, vp, tbl, idx, cnt, thr, pos):
        return sata_decode_attention_paged_kernel(
            q, kp, vp, tbl, idx, cnt, thr, pos, interpret=interpret)

    out_p = clock.compile(paged, q, kp, vp, tbl, idx, cnt, thr, pos)(
        q, kp, vp, tbl, idx, cnt, thr, pos)
    return (_errors(out_c, out_ref, mag), _errors(out_p, out_ref, mag),
            bool(jnp.array_equal(out_c, out_p)))


def prefill_kernel_parity(clock, *, bh, s, d, blk, topk, interpret,
                          seed=1, ref_chunk=32):
    """Threshold-mode causal compact kernel vs an fp32 reference whose
    selection and plan are made on the host.  Returns its ``_errors``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.sata_attention import sata_block_attention_compact

    rng = np.random.default_rng(seed)
    bf = jnp.bfloat16
    q = jnp.asarray(_grid_values(rng, (bh, s, d)), bf)
    k = jnp.asarray(_grid_values(rng, (bh, s, d)), bf)
    v = jnp.asarray(rng.standard_normal((bh, s, d)), bf)
    scale = 1.0 / np.sqrt(d)
    nb = s // blk
    tok = np.arange(s, dtype=np.int32)
    adm = tok[None, :] <= tok[:, None]                       # causal

    def scores(q, k):
        with jax.default_matmul_precision("highest"):
            raw = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                             k.astype(jnp.float32))
        sc = jax.lax.optimization_barrier(raw) * scale
        t = jnp.arange(s)
        return sc, _topk_threshold(sc, (t[None, :] <= t[:, None])[None], topk)

    def attend(sc, sel, v):
        return _ref_attention(sc, sel, v, "bqk,bkd->bqd")

    n = min(ref_chunk, bh)
    scores_c = clock.compile(scores, q[:n], k[:n])
    attend_c = None
    outs, mags, thrs, bms = [], [], [], []
    for i in range(0, bh, n):
        sc, thr = scores_c(q[i:i + n], k[i:i + n])
        sel = _host_select(sc, thr, adm[None])
        bms.append(sel.reshape(-1, nb, blk, nb, blk).any(axis=(2, 4)))
        sel = jnp.asarray(sel)
        if attend_c is None:
            attend_c = clock.compile(attend, sc, sel, v[:n])
        out, mag = attend_c(sc, sel, v[i:i + n])
        del sc, sel
        outs.append(out)
        mags.append(mag)
        thrs.append(thr)
    out_ref, mag, thr = (jnp.concatenate(x) for x in (outs, mags, thrs))
    idx, cnt = (jnp.asarray(a) for a in _host_plan(np.concatenate(bms)))
    pos = jnp.broadcast_to(jnp.asarray(tok)[None, :, None], (bh, s, 1))

    def kernel(q, k, v, idx, cnt, thr, pos):
        return sata_block_attention_compact(
            q, k, v, idx, cnt, thresholds=thr, q_pos=pos, k_pos=pos,
            causal=True, q_block=blk, k_block=blk, interpret=interpret)

    out = clock.compile(kernel, q, k, v, idx, cnt, thr, pos)(
        q, k, v, idx, cnt, thr, pos)
    return _errors(out, out_ref, mag)


# ---------------------------------------------------------------------------
# phases 2-3: serve and route parity at full width
# ---------------------------------------------------------------------------

def smoke_config(cfg, *, block):
    """The served configuration: bisect thresholds (the SATA predicate,
    so both routes select by the same rule), SATA decode forced on,
    paged pool with page == decode block."""
    from repro.models.config import KVCacheConfig, SataDecodeConfig
    return dataclasses.replace(
        cfg, topk_impl="bisect",
        sata=dataclasses.replace(
            cfg.sata, decode=SataDecodeConfig(mode="on", block=block)),
        kv=KVCacheConfig(layout="paged", page_size=block))


def with_decode_mode(cfg, mode):
    return dataclasses.replace(cfg, sata=dataclasses.replace(
        cfg.sata, decode=dataclasses.replace(cfg.sata.decode, mode=mode)))


def first_decode_logits(clock, cfg, params, prompt, *, slots, max_len):
    """Prefill ``prompt`` into slot 0 of a ``slots``-wide paged cache and
    run one decode step, as ``serve()`` claims a slot.  Returns
    (prefill logits (V,), decode logits (V,), compiled step text)."""
    import jax
    import jax.numpy as jnp
    from repro.core.paging import PageAllocator
    from repro.models import attention as attn
    from repro.models import decode as dec

    page = attn.kv_page_size(cfg, max_len)
    cache = dec.init_cache(cfg, slots, max_len)
    n_pages = int(cache["kv"]["k_pages"].shape[1])
    alloc = PageAllocator(n_pages, slots, max_len // page, page)
    for i in range(slots):
        cache = dec.release_slot(cfg, cache, i)
    cache = dec.reset_slot(cfg, cache, 0)
    plen = prompt.shape[1]
    check(alloc.ensure(0, plen), "pool cannot map one prompt")
    cache = dec.set_page_table(cfg, cache, alloc.table)

    def prefill(p, t):
        return dec.prefill_prompt(p, cfg, t, max_len)

    lg0, state = clock.compile(prefill, params, prompt)(params, prompt)
    cache = dec.install_prefill(cfg, cache, 0, state,
                                alloc.table[0, :alloc.pages_for(plen)])
    del state
    tokens = jnp.zeros((slots, 1), jnp.int32).at[0, 0].set(
        jnp.argmax(lg0[0]).astype(jnp.int32))
    pos = jnp.zeros((slots,), jnp.int32).at[0].set(plen)

    def step(p, c, t, ps):
        return dec.serve_step(p, cfg, c, t, ps)

    compiled = clock.compile(step, params, cache, tokens, pos)
    logits, _ = compiled(params, cache, tokens, pos)
    return (lg0[0].astype(jnp.float32), logits[0, 0].astype(jnp.float32),
            compiled.as_text())


def one_chip(args) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs.archs import ARCHS
    from repro.launch.serve import ServeOptions, serve
    from repro.models import model as mdl

    clock = Clock()
    contiguous, paged, same = decode_kernel_parity(
        clock, b=B, kv=KV, g=G, d=D, s=S, blk=BLK, topk=TOPK,
        interpret=False)
    prefill = prefill_kernel_parity(
        clock, bh=B * KV * G, s=S, d=D, blk=BLK, topk=TOPK, interpret=False)
    say(f"kernel parity vs fp32 reference (B={B} KV={KV} G={G} D={D} "
        f"S={S} block={BLK} top-{TOPK} bf16), error in units of "
        f"mag+|ref| (tol {KERNEL_TOL!r}):")
    for name, (err, rel) in (("contiguous decode", contiguous),
                             ("paged decode", paged),
                             ("threshold causal prefill", prefill)):
        say(f"  {name}: max_err={err!r} max_err/(mag+|ref|)={rel!r}")
        check(rel <= KERNEL_TOL, f"{name} kernel off the reference "
              f"({rel} > {KERNEL_TOL})")
    say(f"  paged == contiguous bitwise: {same}")
    check(same, "paged and contiguous decode kernels disagree")

    cfg = smoke_config(ARCHS["qwen3-4b"], block=BLK)
    opts = ServeOptions(n_requests=8, batch_slots=4, gen_len=32,
                        max_len=S, prompt_len=1024)
    say(f"serving {cfg.name}: {cfg.n_layers} layers, d_model "
        f"{cfg.d_model}, {cfg.n_heads}/{cfg.n_kv_heads} heads, head_dim "
        f"{cfg.hd}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, {cfg.dtype}; "
        f"{opts.n_requests} requests x {opts.prompt_len}+{opts.gen_len} "
        f"tokens over {opts.batch_slots} slots")
    t0 = time.perf_counter()
    out = serve("qwen3-4b", smoke=False, cfg=cfg, seed=args.seed,
                options=opts)
    serve_s = time.perf_counter() - t0
    done = {r: len(t) for r, t in out["outputs"].items()}
    say(f"serve: {out['tokens_generated']} tokens generated over "
        f"{len(done)} requests in {out['steps']} steps; serve() wall "
        f"{serve_s!r} s (init + compile + run)")
    check(sorted(done) == list(range(opts.n_requests))
          and all(n == opts.gen_len for n in done.values())
          and not out["timed_out"],
          f"not every request completed: {done}, timed out "
          f"{out['timed_out']}")
    check("decode_fetch" in out, "serve report has no decode_fetch: the "
          "SATA decode route was not taken")
    f = out["decode_fetch"]
    say(f"decode_fetch: kv_fetch_tiles_plan={f['kv_fetch_tiles_plan']} "
        f"kv_fetch_tiles_dense={f['kv_fetch_tiles_dense']} "
        f"replans={f['replans']!r}")
    check(0 < f["kv_fetch_tiles_plan"] <= f["kv_fetch_tiles_dense"],
          "planned fetch tiles out of range")
    del out

    params = jax.jit(lambda key: mdl.init_params(key, cfg))(
        jax.random.PRNGKey(args.seed))
    prompt = jnp.asarray(np.random.default_rng(args.seed).integers(
        0, cfg.vocab_size, (1, opts.prompt_len)), jnp.int32)
    lg0, sata_lg, text = first_decode_logits(
        clock, cfg, params, prompt, slots=opts.batch_slots, max_len=S)
    check("tpu_custom_call" in text, "no Pallas kernel in the compiled "
          "SATA decode step")
    del text
    off = with_decode_mode(cfg, "off")
    lg0_off, dense_lg, _ = first_decode_logits(
        clock, off, params, prompt, slots=opts.batch_slots, max_len=S)
    for name, x in (("prefill", lg0), ("prefill (dense)", lg0_off),
                    ("SATA decode", sata_lg), ("dense decode", dense_lg)):
        check(bool(jnp.isfinite(x).all()), f"{name} logits not finite")
    diff = float(jnp.max(jnp.abs(sata_lg - dense_lg)))
    rel = diff / float(jnp.max(jnp.abs(dense_lg)))
    say(f"route parity, first decode step logits: SATA vs dense max "
        f"abs diff {diff!r}, relative {rel!r} (tol {ROUTE_TOL}); "
        f"argmax {int(jnp.argmax(sata_lg))} vs {int(jnp.argmax(dense_lg))}")
    check(rel <= ROUTE_TOL, f"SATA route off the dense route ({rel} > "
          f"{ROUTE_TOL})")
    say(f"compile seconds (explicit AOT compiles): {clock.compile_s!r}")


# ---------------------------------------------------------------------------
# four chips: the mesh paths beside their single-device twins
# ---------------------------------------------------------------------------

def tp_decode_parity(mesh, *, b, kv, g, d, s, blk, topk, seed=2):
    """``tensor_parallel_decode_step`` vs the same step on one device.
    Returns (max_err, plans_equal, devices the output spans)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.decode_plan import (decode_plan_update,
                                        init_decode_plan,
                                        update_block_summaries)
    from repro.kernels.ops import sata_decode_attention
    from repro.launch import mesh as M

    rng = np.random.default_rng(seed)
    bf = jnp.bfloat16
    pos = jnp.asarray(np.linspace(s - 1, blk - 1, b).astype(np.int32))
    live = jnp.arange(s)[None, :, None, None] <= pos[:, None, None, None]
    kc = jnp.where(live, jnp.asarray(rng.standard_normal((b, s, kv, d)), bf),
                   0).astype(bf)
    vc = jnp.where(live, jnp.asarray(rng.standard_normal((b, s, kv, d)), bf),
                   0).astype(bf)
    qg = jnp.asarray(rng.standard_normal((b, kv, g, d)), bf)
    kn = jnp.take_along_axis(kc, pos[:, None, None, None], axis=1)

    @jax.jit
    def single(qg, kc, vc, kn, pos, plan):
        plan = update_block_summaries(plan, kn, pos, k_block=blk)
        plan, thr = decode_plan_update(plan, qg, kc, pos, topk_k=topk,
                                       k_block=blk, replan_interval=1)
        return sata_decode_attention(qg, kc, vc, plan["kv_indices"],
                                     plan["kv_counts"], thr, pos,
                                     k_block=blk), plan

    out_r, plan_r = single(qg, kc, vc, kn, pos,
                           init_decode_plan(b, kv, s, d, blk))
    out_s, plan_s = M.tensor_parallel_decode_step(
        mesh, qg, kc, vc, kn, pos, init_decode_plan(b, kv, s, d, blk),
        topk_k=topk, k_block=blk, replan_interval=1)
    err = float(jnp.max(jnp.abs(out_s.astype(jnp.float32)
                                - out_r.astype(jnp.float32))))
    plans_equal = all(bool(jnp.array_equal(plan_s[n], plan_r[n]))
                      for n in plan_r)
    return err, plans_equal, out_s.sharding.device_set, float(
        jnp.max(jnp.abs(vc.astype(jnp.float32))))


def seq_sharded_parity(mesh, *, bh, s, d, blk, topk, seed=3):
    """``sequence_sharded_attention`` vs ``sequence_local_attention``.
    Returns (max_err, max|v|, devices the output spans)."""
    import jax.numpy as jnp
    import numpy as np
    from repro.launch import mesh as M

    rng = np.random.default_rng(seed)
    bf = jnp.bfloat16
    q, k, v = (jnp.asarray(rng.standard_normal((bh, s, d)), bf)
               for _ in range(3))
    ref, _ = M.sequence_local_attention(q, k, v, k_sel=topk, q_block=blk,
                                        k_block=blk)
    out, _ = M.sequence_sharded_attention(mesh, q, k, v, k_sel=topk,
                                          q_block=blk, k_block=blk)
    err = float(jnp.max(jnp.abs(out - ref)))
    return err, float(jnp.max(jnp.abs(v.astype(jnp.float32)))), \
        out.sharding.device_set


def four_chips(args) -> None:
    import jax
    from repro.launch import mesh as M

    n = args.chips
    mesh = M.make_shard_mesh(n)
    mesh_devs = set(mesh.devices.flat)
    check(len(mesh_devs) == n and mesh_devs == set(jax.devices()[:n]),
          f"mesh spans {len(mesh_devs)} devices, not {n}")
    say(f"mesh {dict(mesh.shape)} over devices "
        f"{sorted(d.id for d in mesh_devs)}")
    err, plans_equal, devs, vmax = tp_decode_parity(
        mesh, b=B, kv=KV, g=G, d=D, s=S, blk=BLK, topk=TOPK)
    tol = TWIN_TOL * vmax
    say(f"tensor-parallel decode (KV={KV} over {n} chips, "
        f"{KV // n} heads each; B={B} G={G} D={D} S={S} bf16) vs one "
        f"device: max_err={err!r} tol={tol!r}, plans bitwise equal: "
        f"{plans_equal}, output on {len(devs)} devices")
    check(devs == mesh_devs, "TP decode output does not span the mesh")
    check(err <= tol, f"TP decode off its single-device twin ({err})")
    check(plans_equal, "TP decode plan differs from the single-device plan")
    err, vmax, devs = seq_sharded_parity(
        mesh, bh=G * KV, s=S, d=D, blk=BLK, topk=TOPK)
    tol = TWIN_TOL * vmax
    say(f"sequence-sharded attention ({n}-way; BH={G * KV} S={S} D={D} "
        f"bf16) vs one device: max_err={err!r} tol={tol!r}, output on "
        f"{len(devs)} devices")
    check(devs == mesh_devs, "sequence-sharded output does not span the "
          "mesh")
    check(err <= tol, f"sequence-sharded attention off its single-device "
          f"twin ({err})")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh paths across four chips")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    args = ap.parse_args(argv)
    # before JAX loads the TPU runtime, which would otherwise log to
    # /tmp/tpu_logs
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if not os.path.isdir(os.path.join(SRC, "repro")):
        fail(f"the repository's sources are not next to this script "
             f"(no {os.path.join(SRC, 'repro')})")
    from repro.launch.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX found {dev.platform} ({dev.device_kind})")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} devices, JAX sees "
             f"{len(devices)}")
    from repro.kernels.ops import default_interpret
    check(not default_interpret(), "kernels would run interpreted")
    say(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"compile cache: {cache_dir}")
    t0 = time.perf_counter()
    if args.chips == 1:
        one_chip(args)
    else:
        four_chips(args)
    stats = dev.memory_stats() or {}
    say(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
        f"(device 0); wall {time.perf_counter() - t0!r} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
