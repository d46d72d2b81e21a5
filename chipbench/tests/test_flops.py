"""Operation and byte counts against sums worked out by hand."""
import flops

OLMO = {"n_layers": 16, "d_model": 2048, "n_heads": 16, "n_kv_heads": 16,
        "head_dim": 128, "d_ff": 8192, "vocab_size": 50304, "topk_k": 64,
        "mlp_variant": "swiglu"}
QWEN = {"n_layers": 36, "d_model": 2560, "n_heads": 32, "n_kv_heads": 8,
        "head_dim": 128, "d_ff": 9728, "vocab_size": 151936, "topk_k": 64,
        "mlp_variant": "swiglu"}


def test_layer_params():
    # q, o: 2048·2048 each; k, v: the same (MHA); MLP 3·2048·8192
    assert flops.layer_matmul_params(OLMO) == 4 * 2048 ** 2 + 3 * 2048 * 8192
    # q, o: 2560·4096 each; k, v: 2560·1024 each; MLP 3·2560·9728
    assert flops.layer_matmul_params(QWEN) == (
        2 * 2560 * 4096 + 2 * 2560 * 1024 + 3 * 2560 * 9728)


def test_attention_keeps_topk():
    # 10 visible keys, all kept (fewer than 64)
    assert flops.attn_flops(OLMO, 10) == 16 * 2 * 2048 * 20
    # 1000 visible keys: all scored, 64 mixed
    assert flops.attn_flops(OLMO, 1000) == 16 * 2 * 2048 * (1000 + 64)


def test_prefill_and_decode_step():
    m = OLMO
    body = 2 * 16 * flops.layer_matmul_params(m)
    assert flops.decode_step_flops(m, 7) == (
        body + flops.attn_flops(m, 8) + 2 * 2048 * 50304)
    assert flops.prefill_flops(m, 2) == (
        2 * body + flops.attn_flops(m, 1) + flops.attn_flops(m, 2)
        + 2 * 2048 * 50304)


def test_decode_kernel_call():
    # 2 slots, one tile each per KV head; G = 4 query heads share a tile
    fl, nb = flops.decode_kernel_call(QWEN, slots=2, tiles=16, block=128,
                                      plan_blocks=9)
    assert fl == 16 * 4 * 4 * 128 * 128
    tiles = 16 * 2 * 128 * 128 * 2
    qo = 2 * 2 * 32 * 128 * 2
    plan = 4 * (2 * 8 * 9 + 2 * 8 + 2 + 2 * 32)
    assert nb == tiles + qo + plan
