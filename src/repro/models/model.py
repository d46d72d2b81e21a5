"""Model assembly: family-specific stacks with scan-over-layers + remat.

Families
  dense / moe   — pre-norm decoder (attn + mlp|moe), scanned
  vlm           — decoder with a cross-attention layer every
                  ``cross_attn_period`` layers (grouped nested scan)
  hybrid        — Mamba2 backbone with a *shared* attention block every
                  ``hybrid_period`` layers (zamba2)
  audio         — encoder-decoder (whisper backbone; frontend stubbed to
                  precomputed frame embeddings)
  ssm           — RWKV6 stack (attention-free)

All stacks scan over stacked layer params (bounded HLO for 95-100 layer
models) with a configurable remat policy.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.ctx import constrain
from repro.models import attention as attn
from repro.models import mamba2, moe, rwkv6
from repro.models.config import ModelConfig
from repro.models.layers import (Params, _dtype, apply_norm, embed_apply,
                                 embed_init, mlp_apply, mlp_init, norm_init,
                                 unembed_apply, dense_init)


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def _decoder_block_init(key, cfg: ModelConfig, cross: bool = False) -> Params:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {"ln1": norm_init(cfg, cfg.d_model),
         "attn": attn.attention_init(k1, cfg, cross=cross),
         "ln2": norm_init(cfg, cfg.d_model)}
    if cfg.moe:
        p["moe"] = moe.moe_init(k2, cfg)
    else:
        p["mlp"] = mlp_init(k3, cfg, cfg.d_model, cfg.d_ff)
    if cross:
        p["lnx"] = norm_init(cfg, cfg.d_model)
    return p


def _decoder_block_apply(p: Params, cfg, x, causal=None):
    aux = jnp.zeros((), jnp.float32)
    h = apply_norm(p["ln1"], cfg, x)
    x = x + attn.attention_apply(p["attn"], cfg, h, causal=causal)
    h = apply_norm(p["ln2"], cfg, x)
    if cfg.moe:
        y, aux = moe.moe_apply(p["moe"], cfg, h)
        x = x + y
    else:
        x = x + mlp_apply(p["mlp"], cfg, h)
    return x, aux


def _cross_block_init(key, cfg) -> Params:
    """VLM cross-attention layer (llama-3.2-vision style gated x-attn)."""
    k1, k2 = jax.random.split(key)
    return {"ln1": norm_init(cfg, cfg.d_model),
            "attn": attn.attention_init(k1, cfg, cross=True),
            "gate": jnp.zeros((), jnp.float32),
            "ln2": norm_init(cfg, cfg.d_model),
            "mlp": mlp_init(k2, cfg, cfg.d_model, cfg.d_ff),
            "gate_mlp": jnp.zeros((), jnp.float32)}


def _cross_block_apply(p: Params, cfg, x, context):
    h = apply_norm(p["ln1"], cfg, x)
    y = attn.attention_apply(p["attn"], cfg, h, kv_src=context)
    x = x + jnp.tanh(p["gate"]).astype(x.dtype) * y
    h = apply_norm(p["ln2"], cfg, x)
    x = x + jnp.tanh(p["gate_mlp"]).astype(x.dtype) * mlp_apply(p["mlp"], cfg, h)
    return x


def _encdec_dec_block_init(key, cfg) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {"ln1": norm_init(cfg, cfg.d_model),
            "attn": attn.attention_init(k1, cfg),
            "lnx": norm_init(cfg, cfg.d_model),
            "attn_cross": attn.attention_init(k2, cfg, cross=True),
            "ln2": norm_init(cfg, cfg.d_model),
            "mlp": mlp_init(k3, cfg, cfg.d_model, cfg.d_ff)}


def _encdec_dec_block_apply(p: Params, cfg, x, context):
    h = apply_norm(p["ln1"], cfg, x)
    x = x + attn.attention_apply(p["attn"], cfg, h)
    h = apply_norm(p["lnx"], cfg, x)
    x = x + attn.attention_apply(p["attn_cross"], cfg, h, kv_src=context)
    h = apply_norm(p["ln2"], cfg, x)
    return x + mlp_apply(p["mlp"], cfg, h)


def _mamba_block_init(key, cfg) -> Params:
    return {"ln": norm_init(cfg, cfg.d_model),
            "mixer": mamba2.mamba2_init(key, cfg)}


def _mamba_block_apply(p: Params, cfg, x):
    return x + mamba2.mamba2_apply(p["mixer"], cfg,
                                   apply_norm(p["ln"], cfg, x))


def _rwkv_block_init(key, cfg) -> Params:
    k1, k2 = jax.random.split(key)
    return {"ln1": norm_init(cfg, cfg.d_model),
            "tmix": rwkv6.rwkv6_init(k1, cfg),
            "ln2": norm_init(cfg, cfg.d_model)}


def _rwkv_block_apply(p: Params, cfg, x):
    h = apply_norm(p["ln1"], cfg, x)
    y, _, _ = rwkv6.rwkv6_time_mix(p["tmix"], cfg, h)
    x = x + y
    h = apply_norm(p["ln2"], cfg, x)
    y, _ = rwkv6.rwkv6_channel_mix(p["tmix"], cfg, h)
    return x + y


# ---------------------------------------------------------------------------
# Stacked-scan helpers
# ---------------------------------------------------------------------------

def _stack_init(key, n: int, init_fn):
    keys = jax.random.split(key, n)
    return jax.vmap(init_fn)(keys)


def _remat(cfg, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "full":
        return jax.checkpoint(fn)
    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)


def maybe_scan(cfg, f, carry, xs):
    """lax.scan, or an unrolled Python loop when ``cfg.scan_layers`` is
    False (XLA cost analysis counts a while body once regardless of trip
    count, so a cost probe must unroll)."""
    if cfg.scan_layers:
        return jax.lax.scan(f, carry, xs)
    n = jax.tree_util.tree_leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        x_i = jax.tree.map(lambda a: a[i], xs)
        carry, y = f(carry, x_i)
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    else:
        ys = None
    return carry, ys


def _scan_stack(cfg, stacked: Params, x, body):
    """scan x through stacked layer params, accumulating aux losses."""
    def scan_body(carry, layer_params):
        h, aux = carry
        h, a = body(layer_params, h)
        return (constrain(h, "act"), aux + a), None

    (x, aux), _ = maybe_scan(cfg, _remat(cfg, scan_body),
                             (x, jnp.zeros((), jnp.float32)), stacked)
    return x, aux


# ---------------------------------------------------------------------------
# init / forward
# ---------------------------------------------------------------------------

def init_params(key, cfg: ModelConfig) -> Params:
    kemb, kstack, kextra, kfinal = jax.random.split(key, 4)
    params: Params = {"embed": embed_init(kemb, cfg),
                      "final_ln": norm_init(cfg, cfg.d_model)}

    if cfg.family in ("dense", "moe"):
        params["layers"] = _stack_init(
            kstack, cfg.n_layers, lambda k: _decoder_block_init(k, cfg))

    elif cfg.family == "vlm":
        n_cross = cfg.n_layers // cfg.cross_attn_period
        n_self = cfg.n_layers - n_cross
        per_group = n_self // n_cross
        self_stack = _stack_init(
            kstack, n_self, lambda k: _decoder_block_init(k, cfg))
        # regroup leaf arrays (L_self, ...) → (G, per_group, ...)
        params["layers"] = jax.tree.map(
            lambda a: a.reshape((n_cross, per_group) + a.shape[1:]),
            self_stack)
        params["cross_layers"] = _stack_init(
            kextra, n_cross, lambda k: _cross_block_init(k, cfg))

    elif cfg.family == "hybrid":
        params["layers"] = _stack_init(
            kstack, cfg.n_layers, lambda k: _mamba_block_init(k, cfg))
        params["shared_attn"] = _decoder_block_init(kextra, cfg)
        params["shared_in"] = dense_init(
            jax.random.fold_in(kextra, 1), 2 * cfg.d_model, cfg.d_model,
            _dtype(cfg))

    elif cfg.family == "audio":
        params["enc_layers"] = _stack_init(
            kextra, cfg.encoder_layers, lambda k: _decoder_block_init(k, cfg))
        params["enc_ln"] = norm_init(cfg, cfg.d_model)
        params["layers"] = _stack_init(
            kstack, cfg.n_layers, lambda k: _encdec_dec_block_init(k, cfg))

    elif cfg.family == "ssm":
        params["layers"] = _stack_init(
            kstack, cfg.n_layers, lambda k: _rwkv_block_init(k, cfg))

    else:
        raise ValueError(cfg.family)
    return params


def _run_encoder(params, cfg, audio_embeds):
    def body(p, h):
        h, aux = _decoder_block_apply(p, cfg, h, causal=False)
        return h, aux
    x, _ = _scan_stack(cfg, params["enc_layers"], audio_embeds, body)
    return apply_norm(params["enc_ln"], cfg, x)


def forward(params: Params, cfg: ModelConfig, batch: Dict[str, jax.Array]
            ) -> Tuple[jax.Array, jax.Array]:
    """→ (logits fp32 (B,S,V), aux_loss)."""
    x = embed_apply(params["embed"], batch["tokens"]).astype(_dtype(cfg))
    x = constrain(x, "act")
    aux = jnp.zeros((), jnp.float32)

    if cfg.family in ("dense", "moe"):
        def body(p, h):
            return _decoder_block_apply(p, cfg, h)
        x, aux = _scan_stack(cfg, params["layers"], x, body)

    elif cfg.family == "vlm":
        context = batch["image_embeds"].astype(_dtype(cfg))

        def outer(carry, inp):
            h, aux = carry
            self_group, cross_p = inp

            def body(p, hh):
                return _decoder_block_apply(p, cfg, hh)
            h, a = _scan_stack(cfg, self_group, h, body)
            h = _remat(cfg, lambda p, hh: _cross_block_apply(
                p, cfg, hh, context))(cross_p, h)
            return (h, aux + a), None

        (x, aux), _ = maybe_scan(
            cfg, outer, (x, aux), (params["layers"], params["cross_layers"]))

    elif cfg.family == "hybrid":
        x0 = x
        period = cfg.hybrid_period
        n_groups = cfg.n_layers // period
        grouped = jax.tree.map(
            lambda a: a.reshape((n_groups, period) + a.shape[1:]),
            params["layers"])

        def outer(carry, group_params):
            h, aux = carry

            def body(p, hh):
                return _mamba_block_apply(p, cfg, hh), jnp.zeros((), jnp.float32)
            h, a = _scan_stack(cfg, group_params, h, body)
            # shared attention block on concat(hidden, embeddings); only the
            # block's *delta* feeds back into the backbone (zamba2-style).
            cat = jnp.concatenate([h, x0], axis=-1) @ params["shared_in"]
            y, a2 = _decoder_block_apply(params["shared_attn"], cfg, cat)
            return (h + (y - cat), aux + a + a2), None

        (x, aux), _ = maybe_scan(cfg, outer, (x, aux), grouped)

    elif cfg.family == "audio":
        context = _run_encoder(params, cfg,
                               batch["audio_embeds"].astype(_dtype(cfg)))

        def body(p, h):
            return _encdec_dec_block_apply(p, cfg, h, context), \
                jnp.zeros((), jnp.float32)
        x, aux = _scan_stack(cfg, params["layers"], x, body)

    elif cfg.family == "ssm":
        def body(p, h):
            return _rwkv_block_apply(p, cfg, h), jnp.zeros((), jnp.float32)
        x, aux = _scan_stack(cfg, params["layers"], x, body)

    x = apply_norm(params["final_ln"], cfg, x)
    logits = constrain(unembed_apply(params["embed"], cfg, x), "logits")
    return logits, aux


def loss_fn(params: Params, cfg: ModelConfig, batch: Dict[str, jax.Array]
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token cross-entropy (labels pre-shifted by the pipeline)."""
    logits, aux = forward(params, cfg, batch)
    labels = batch["labels"]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    mask = batch.get("loss_mask", jnp.ones_like(nll))
    loss = (nll * mask).sum() / jnp.clip(mask.sum(), 1.0)
    total = loss + 0.01 * aux
    return total, {"nll": loss, "aux": aux}
