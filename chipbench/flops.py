"""Operation and byte counts of the shared decode kernel, and the
model-FLOP shares the readers report, from shapes alone.

``m`` is the ``model`` group of a configuration file
(``configs/<name>.json``): the fields the program's ``ModelConfig``
takes.  Nothing here reads the program.  A model's own count (FLOPs of
a prefill and of a decode step) lives in the reference module its
configuration names (``refs/dense.py`` and its interface), which a run
carries as ``Run.ref``.
"""
from __future__ import annotations


def head_dim(m: dict) -> int:
    return int(m.get("head_dim") or m["d_model"] // m["n_heads"])


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
    one step's model FLOPs for every busy slot (the run's reference
    module's ``decode_step_flops``, averaged over the positions a
    request's generated tokens are written at) over the step period
    ``period_s``."""
    s = run.shape
    positions = range(s.prompt_len, s.prompt_len + s.gen_len - 1)
    if not positions or not period_s:
        return None
    per_slot = sum(run.ref.decode_step_flops(run.model, p)
                   for p in positions) / len(positions)
    slots = min(s.slots, s.requests_per_job)
    return 100.0 * slots * per_slot / period_s / run.peak["bf16_flops_per_s"]


def prefill_mfu(run):
    """Percent of the chip's peak FLOP/s that the window's prefills
    carried: requests per second over the whole jobs (host clock) times
    one prompt's prefill FLOPs (the run's reference module's
    ``prefill_flops``)."""
    rps = run.rate("requests")
    if rps is None:
        return None
    f = run.ref.prefill_flops(run.model, run.shape.prompt_len)
    return 100.0 * rps * f / run.peak["bf16_flops_per_s"]
