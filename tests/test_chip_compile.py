"""Ahead-of-time compiles for a described TPU v5e chip.

Interpret-mode tests cannot see what Mosaic refuses (block tiling,
SMEM budget, bf16 compares on a chip without a bf16 VPU).  These tests
compile the main-path kernels at qwen3-4b widths — and one full-width
``serve_step`` — for a v5e chip described by the installed TPU
compiler, with nothing attached, and check that each compiled program
holds its Pallas kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library.  Kept in
one file so a single worker loads it.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.kernels.ops as ops
from repro.configs.archs import ARCHS
from repro.kernels.sata_attention import (sata_block_attention,
                                          sata_block_attention_compact)
from repro.kernels.sata_decode import (sata_decode_attention_kernel,
                                       sata_decode_attention_paged_kernel)
from repro.models.config import KVCacheConfig, SataDecodeConfig

# qwen3-4b attention widths: 4 slots, 8 KV heads x 4 query heads,
# head_dim 128, a 2048-token cache in 128-token blocks, bf16
B, KV, G, D, S, BLK = 4, 8, 4, 128, 2048, 128
BH, NB = B * KV * G, S // BLK
HBM_BYTES = 16 * 2 ** 30                  # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    # the TPU library loads below and would otherwise write its logs to
    # /tmp/tpu_logs; it reads this when it loads
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    # traces cached earlier in this process were made for the CPU and
    # chose interpret mode; start clean
    jax.clear_caches()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()
    # and drop the traces made here for the chip (compiled, not
    # interpreted) before later tests in this process run on the CPU
    jax.clear_caches()


def _sd(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_decode_kernel_compiles(one_chip):
    i32 = jnp.int32
    text = _compiled_text(
        lambda q, k, v, idx, cnt, thr, pos: sata_decode_attention_kernel(
            q, k, v, idx, cnt, thr, pos, k_block=BLK),
        _sd(one_chip, (B, KV, G, D)), _sd(one_chip, (B, S, KV, D)),
        _sd(one_chip, (B, S, KV, D)), _sd(one_chip, (B, KV, NB), i32),
        _sd(one_chip, (B, KV), i32), _sd(one_chip, (B, KV, G, 1),
                                         jnp.float32),
        _sd(one_chip, (B,), i32))
    assert "tpu_custom_call" in text


def test_paged_decode_kernel_compiles(one_chip):
    i32 = jnp.int32
    n_pages = B * NB + 1
    text = _compiled_text(
        lambda q, kp, vp, tbl, idx, cnt, thr, pos:
        sata_decode_attention_paged_kernel(q, kp, vp, tbl, idx, cnt, thr,
                                           pos),
        _sd(one_chip, (B, KV, G, D)), _sd(one_chip, (n_pages, BLK, KV, D)),
        _sd(one_chip, (n_pages, BLK, KV, D)), _sd(one_chip, (B, NB), i32),
        _sd(one_chip, (B, KV, NB), i32), _sd(one_chip, (B, KV), i32),
        _sd(one_chip, (B, KV, G, 1), jnp.float32), _sd(one_chip, (B,), i32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("causal", [True, False])
def test_threshold_compact_kernel_compiles(one_chip, causal):
    i32 = jnp.int32
    text = _compiled_text(
        lambda q, k, v, idx, cnt, thr, pos: sata_block_attention_compact(
            q, k, v, idx, cnt, thresholds=thr, q_pos=pos, k_pos=pos,
            causal=causal, q_block=BLK, k_block=BLK),
        _sd(one_chip, (BH, S, D)), _sd(one_chip, (BH, S, D)),
        _sd(one_chip, (BH, S, D)), _sd(one_chip, (BH, NB, NB), i32),
        _sd(one_chip, (BH, NB), i32), _sd(one_chip, (BH, S, 1), jnp.float32),
        _sd(one_chip, (BH, S, 1), i32))
    assert "tpu_custom_call" in text


def test_dense_grid_kernel_compiles(one_chip):
    text = _compiled_text(
        lambda q, k, v, bm: sata_block_attention(q, k, v, bm, q_block=BLK,
                                                 k_block=BLK),
        _sd(one_chip, (BH, S, D)), _sd(one_chip, (BH, S, D)),
        _sd(one_chip, (BH, S, D)), _sd(one_chip, (BH, NB, NB), jnp.int32))
    assert "tpu_custom_call" in text


# The prefill kernels hold their plan in scalar memory (SMEM, 1 MiB on a
# v5e core): BH·nqb·P words of kv_indices plus BH·nqb of counts for the
# compact kernel, BH·nqb·nkb words of block map for the dense grid, each
# rounded up to 4 KiB.  For one qwen3-4b prompt (32 query heads) with a
# full plan (P = nkb) the longest prompts that fit are 88 blocks (11264
# tokens) and 90 blocks (11520 tokens); one block more is refused.
@pytest.mark.parametrize("kernel,s", [("compact", 88 * BLK),
                                      ("dense_grid", 90 * BLK)])
def test_prefill_kernel_compiles_at_smem_ceiling(one_chip, kernel, s):
    bh, nb, i32 = 32, s // BLK, jnp.int32
    qkv = [_sd(one_chip, (bh, s, D)) for _ in range(3)]
    if kernel == "compact":
        text = _compiled_text(
            lambda q, k, v, idx, cnt, thr, pos: sata_block_attention_compact(
                q, k, v, idx, cnt, thresholds=thr, q_pos=pos, k_pos=pos,
                causal=True, q_block=BLK, k_block=BLK),
            *qkv, _sd(one_chip, (bh, nb, nb), i32),
            _sd(one_chip, (bh, nb), i32),
            _sd(one_chip, (bh, s, 1), jnp.float32),
            _sd(one_chip, (bh, s, 1), i32))
    else:
        text = _compiled_text(
            lambda q, k, v, bm: sata_block_attention(q, k, v, bm, q_block=BLK,
                                                     k_block=BLK),
            *qkv, _sd(one_chip, (bh, nb, nb), i32))
    assert "tpu_custom_call" in text


def test_full_width_serve_step_fits_one_chip(one_chip, monkeypatch):
    """qwen3-4b at its published widths, paged pool of 4 x 2048 tokens,
    SATA decode on: the decode step compiles with the gather kernel in
    it, and its arguments and outputs fit one chip's HBM."""
    from repro.models import decode as dec
    from repro.models import model as mdl
    # the step's kernels ask default_interpret(), which sees this CPU
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    cfg = ARCHS["qwen3-4b"]
    cfg = dataclasses.replace(
        cfg, topk_impl="bisect",
        sata=dataclasses.replace(
            cfg.sata, decode=SataDecodeConfig(mode="on", block=BLK)),
        kv=KVCacheConfig(layout="paged", page_size=BLK))

    def shapes(tree):
        return jax.tree.map(lambda a: _sd(one_chip, a.shape, a.dtype), tree)

    params = shapes(jax.eval_shape(lambda k: mdl.init_params(k, cfg),
                                   jax.random.PRNGKey(0)))
    cache = shapes(jax.eval_shape(lambda: dec.init_cache(cfg, B, S)))
    compiled = jax.jit(
        lambda p, c, t, pos: dec.serve_step(p, cfg, c, t, pos)).lower(
            params, cache, _sd(one_chip, (B, 1), jnp.int32),
            _sd(one_chip, (B,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes \
        < HBM_BYTES, mem
