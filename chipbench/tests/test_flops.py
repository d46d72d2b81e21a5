"""Operation and byte counts against sums worked out by hand, and the
dense models' counts at their cells' shapes pinned."""
import json
from pathlib import Path

import pytest

import flops
from refs import dense

ROOT = Path(__file__).resolve().parents[2]

OLMO = {"family": "dense", "n_layers": 16, "d_model": 2048, "n_heads": 16,
        "n_kv_heads": 16, "head_dim": 128, "d_ff": 8192, "vocab_size": 50304,
        "topk_k": 64, "mlp_variant": "swiglu"}
QWEN = {"family": "dense", "n_layers": 36, "d_model": 2560, "n_heads": 32,
        "n_kv_heads": 8, "head_dim": 128, "d_ff": 9728, "vocab_size": 151936,
        "topk_k": 64, "mlp_variant": "swiglu"}


def test_layer_params():
    # q, o: 2048·2048 each; k, v: the same (MHA); MLP 3·2048·8192
    assert dense.layer_matmul_params(OLMO) == 4 * 2048 ** 2 + 3 * 2048 * 8192
    # q, o: 2560·4096 each; k, v: 2560·1024 each; MLP 3·2560·9728
    assert dense.layer_matmul_params(QWEN) == (
        2 * 2560 * 4096 + 2 * 2560 * 1024 + 3 * 2560 * 9728)


def test_attention_keeps_topk():
    # 10 visible keys, all kept (fewer than 64)
    assert dense.attn_flops(OLMO, 10) == 16 * 2 * 2048 * 20
    # 1000 visible keys: all scored, 64 mixed
    assert dense.attn_flops(OLMO, 1000) == 16 * 2 * 2048 * (1000 + 64)


def test_prefill_and_decode_step():
    m = OLMO
    body = 2 * 16 * dense.layer_matmul_params(m)
    assert dense.decode_step_flops(m, 7) == (
        body + dense.attn_flops(m, 8) + 2 * 2048 * 50304)
    assert dense.prefill_flops(m, 2) == (
        2 * body + dense.attn_flops(m, 1) + dense.attn_flops(m, 2)
        + 2 * 2048 * 50304)


# (configuration, prompt_len, gen_len) of each dense cell, and of
# olmo-1b at the prefill cell's shape, with the counts the harness gave
# before they moved into ``refs/dense.py``: prefill_flops(prompt_len),
# decode_step_flops at the first and the last position ``mfu.decode``
# averages over
PINNED = [
    ("olmo-1b", 1536, 128, 3382411001856, 2458451968, 2466709504),
    ("olmo-1b", 1920, 8, 4252154462208, 2483617792, 2484011008),
    ("qwen3-4b", 1024, 128, 7615311380480, 8365703168, 8402862080),
    ("qwen3-4b", 1920, 8, 14532219895808, 8629944320, 8631713792),
]


@pytest.mark.parametrize("name,prompt,gen,prefill,first,last", PINNED)
def test_pinned_counts(name, prompt, gen, prefill, first, last):
    conf = json.loads((ROOT / f"chipbench/configs/{name}.json").read_text())
    m = {**conf["model"], **conf["program_fixed"]}
    assert dense.prefill_flops(m, prompt) == prefill
    assert dense.decode_step_flops(m, prompt) == first
    assert dense.decode_step_flops(m, prompt + gen - 2) == last


def test_decode_kernel_call():
    # 2 slots, one tile each per KV head; G = 4 query heads share a tile
    fl, nb = flops.decode_kernel_call(QWEN, slots=2, tiles=16, block=128,
                                      plan_blocks=9)
    assert fl == 16 * 4 * 4 * 128 * 128
    tiles = 16 * 2 * 128 * 128 * 2
    qo = 2 * 2 * 32 * 128 * 2
    plan = 4 * (2 * 8 * 9 + 2 * 8 + 2 + 2 * 32)
    assert nb == tiles + qo + plan
