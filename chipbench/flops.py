"""Operation and byte counts of the served models, from shapes alone.

``m`` is the ``model`` group of a configuration file
(``configs/<name>.json``): the fields the program's ``ModelConfig``
takes.  Nothing here reads the program.

Attention is counted as the model defines it: each query scores every
key it may see (``2·H·hd`` per key, which is what an exact top-k
selection needs) and mixes the values of the ``topk_k`` keys it keeps
(``2·H·hd`` per kept key).  Matmul FLOPs are ``2`` per weight.
"""
from __future__ import annotations


def head_dim(m: dict) -> int:
    return int(m.get("head_dim") or m["d_model"] // m["n_heads"])


def layer_matmul_params(m: dict) -> int:
    """Weights of one decoder layer's matmuls (q, k, v, o and the MLP)."""
    d, hd = m["d_model"], head_dim(m)
    attn = 2 * d * m["n_heads"] * hd + 2 * d * m["n_kv_heads"] * hd
    per_ff = 3 if m.get("mlp_variant", "swiglu") == "swiglu" else 2
    return attn + per_ff * d * m["d_ff"]


def unembed_params(m: dict) -> int:
    return m["d_model"] * m["vocab_size"]


def attn_flops(m: dict, ctx: int) -> int:
    """One query position over ``ctx`` visible keys, all layers."""
    per_key = 2 * m["n_heads"] * head_dim(m)
    kept = min(int(m.get("topk_k") or ctx), ctx)
    return m["n_layers"] * per_key * (ctx + kept)


def prefill_flops(m: dict, prompt_len: int) -> int:
    """A causal prefill of ``prompt_len`` tokens that unembeds only the
    last position (the first generated token's logits)."""
    body = 2 * m["n_layers"] * layer_matmul_params(m) * prompt_len
    attn = sum(attn_flops(m, i + 1) for i in range(prompt_len))
    return body + attn + 2 * unembed_params(m)


def decode_step_flops(m: dict, pos: int) -> int:
    """One generated token written at position ``pos`` (``pos + 1``
    visible keys), unembedded."""
    return (2 * m["n_layers"] * layer_matmul_params(m)
            + attn_flops(m, pos + 1) + 2 * unembed_params(m))


def decode_kernel_call(m: dict, *, slots: int, tiles: float, block: int,
                       plan_blocks: int, dtype_bytes: int = 2):
    """(FLOPs, bytes) of one call of the paged decode gather kernel on
    one layer, which fetches ``tiles`` planned (slot, KV head, block)
    tiles of K and V.

    Per tile: ``block·hd`` keys and as many values read; each of the
    ``G = H / KV`` query heads sharing the tile does ``2·block·hd``
    FLOPs for its scores and as many for its output.  Besides the tiles
    the call reads the queries and writes the outputs (``slots·H·hd``
    each, in the cache dtype) and reads its int32 plan: block indices
    (``slots·KV·plan_blocks``), counts, positions and fp32 thresholds.
    """
    hd, kv, h = head_dim(m), m["n_kv_heads"], m["n_heads"]
    g = h // kv
    flops = tiles * 4 * g * block * hd
    tile_bytes = 2 * block * hd * dtype_bytes
    qo_bytes = 2 * slots * h * hd * dtype_bytes
    plan_bytes = 4 * (slots * kv * plan_blocks + slots * kv + slots
                      + slots * h)
    return flops, tiles * tile_bytes + qo_bytes + plan_bytes


def decode_mfu(run, period_s: float):
    """Percent of the chip's peak FLOP/s that the decode steps carried:
    one step's model FLOPs for every busy slot (``decode_step_flops``
    averaged over the positions a request's generated tokens are
    written at) over the step period ``period_s``."""
    s = run.shape
    positions = range(s.prompt_len, s.prompt_len + s.gen_len - 1)
    if not positions or not period_s:
        return None
    per_slot = sum(decode_step_flops(run.model, p)
                   for p in positions) / len(positions)
    slots = min(s.slots, s.requests_per_job)
    return 100.0 * slots * per_slot / period_s / run.peak["bf16_flops_per_s"]


def prefill_mfu(run):
    """Percent of the chip's peak FLOP/s that the window's prefills
    carried: requests per second over the whole jobs (host clock) times
    one prompt's prefill FLOPs."""
    rps = run.rate("requests")
    if rps is None:
        return None
    f = prefill_flops(run.model, run.shape.prompt_len)
    return 100.0 * rps * f / run.peak["bf16_flops_per_s"]
