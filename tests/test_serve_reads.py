"""The serving loop reads the device once a step: the tokens it serves
are those recorded from the loop that read each slot's token twice and
the plan counters apart, in a paged SATA serve, a contiguous one
without a plan, a pool small enough to stall and preempt, and
``gen_len`` 1."""
import dataclasses

import pytest

from repro.configs.archs import SMOKE
from repro.launch.serve import ServeOptions, serve
from repro.models.config import KVCacheConfig, SataConfig, SataDecodeConfig


def _cfg(layout, mode, pool_pages=0):
    return dataclasses.replace(
        SMOKE["qwen3-4b"], topk_impl="bisect",
        kv=KVCacheConfig(layout=layout, pool_pages=pool_pages),
        sata=SataConfig(decode=SataDecodeConfig(mode=mode, block=8,
                                                replan=4)))


_FIRST = {0: [17, 206, 206, 206, 206, 206],
          1: [216, 226, 233, 102, 94, 71],
          2: [32, 5, 50, 224, 73, 229]}

# setup: (cfg, n_requests, batch_slots, gen_len, recorded outputs)
SETUPS = {
    "paged_plan": (_cfg("paged", "on"), 3, 2, 6, _FIRST),
    "contiguous": (_cfg("contiguous", "off"), 3, 2, 6, _FIRST),
    "stall_preempt": (_cfg("paged", "on", pool_pages=4), 3, 3, 16, {
        0: [17, 206, 206, 206, 206, 206, 206, 133, 206, 206, 206, 177,
            177, 177, 132, 5],
        1: [216, 226, 233, 102, 94, 71, 6, 161, 85, 127, 233, 93, 56,
            193, 169, 95],
        2: [32, 5, 50, 224, 73, 229, 59, 5, 224, 19, 128, 177, 187, 174,
            229, 33]}),
    "gen1": (_cfg("paged", "on"), 4, 2, 1,
             {0: [17], 1: [216], 2: [32], 3: [18]}),
}


@pytest.mark.parametrize("name", list(SETUPS))
def test_served_tokens_and_one_read_a_step(name):
    cfg, n_req, slots, gen, recorded = SETUPS[name]
    out = serve("qwen3-4b", cfg=cfg, options=ServeOptions(
        n_requests=n_req, batch_slots=slots, gen_len=gen, max_len=32,
        prompt_len=5))
    assert out["outputs"] == recorded
    # each claim's first token, then one read a step (retirement is off:
    # no sweep reads)
    claims = out["spans"]["serve.prefill"]["count"]
    assert out["pulls"] == claims + out["steps"]
    if name == "stall_preempt":
        occ = out["page_occupancy"]
        assert occ["stalled_steps"] > 0 and occ["preemptions"] > 0
