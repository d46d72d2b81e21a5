"""Data-pipeline determinism + block-plan invariants (property tests)."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.configs.archs import SMOKE
from repro.core.blockmap import bf16_round, bisect_select, sata_block_plan
from repro.core.masks import SyntheticTrace, synthetic_masks
from repro.data.pipeline import SyntheticLM


def test_pipeline_deterministic_across_restart():
    cfg = SMOKE["olmo-1b"]
    p1 = SyntheticLM(cfg, batch=4, seq=16, seed=7)
    batches = [p1.next_batch() for _ in range(5)]
    state = p1.save_state()
    after = [p1.next_batch() for _ in range(3)]
    p2 = SyntheticLM(cfg, batch=4, seq=16, seed=7)
    p2.restore_state(state)
    resumed = [p2.next_batch() for _ in range(3)]
    for a, b in zip(after, resumed):
        np.testing.assert_array_equal(a["tokens"], b["tokens"])
        np.testing.assert_array_equal(a["labels"], b["labels"])


def test_pipeline_labels_are_shifted_tokens():
    cfg = SMOKE["olmo-1b"]
    p = SyntheticLM(cfg, batch=2, seq=32, seed=0)
    b = p.next_batch()
    # labels[t] is the token following tokens[t] in the same stream:
    # tokens[1:] == labels[:-1]
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000), st.integers(2, 6))
def test_property_block_plan_permutations(seed, clusters):
    """kv_order and q_order are valid permutations per head; the block
    map is exactly the occupancy of the doubly-permuted mask."""
    tr = SyntheticTrace(n_tokens=64, k=8, cluster_scale=3.0,
                        discrete_clusters=clusters, noise=0.4)
    masks = jnp.asarray(synthetic_masks(seed, tr, n_heads=2))
    kv, qo, bm = sata_block_plan(masks, 8, 8)
    for h in range(2):
        assert sorted(np.asarray(kv[h]).tolist()) == list(range(64))
        assert sorted(np.asarray(qo[h]).tolist()) == list(range(64))
    perm = jnp.take_along_axis(masks, kv[:, None, :], axis=2)
    perm = jnp.take_along_axis(perm, qo[:, :, None], axis=1)
    occ = perm.reshape(2, 8, 8, 8, 8).any(axis=(2, 4))
    np.testing.assert_array_equal(np.asarray(bm), np.asarray(occ))


def _float_cases(kind):
    rng = np.random.default_rng(0)
    if kind == "random_bits":
        x = rng.integers(0, 2 ** 32, 200_000, dtype=np.uint64)
        x = x.astype(np.uint32).view(np.float32)
    elif kind == "ties":
        # exactly halfway between two bf16 values, both parities
        hi = rng.integers(0, 2 ** 16, 20_000, dtype=np.uint64)
        x = ((hi << 16) | 0x8000).astype(np.uint32).view(np.float32)
    else:
        f = np.finfo(np.float32)
        x = np.array([0.0, -0.0, np.inf, -np.inf, f.max, -f.max, f.tiny,
                      1e-45, -1e30, 1.0, -2.0 ** -133], np.float32)
    return x[np.isfinite(x) | np.isinf(x)]


@pytest.mark.parametrize("kind", ["random_bits", "ties", "special"])
def test_bf16_round_matches_convert(kind):
    """The bit-level rounding the selection predicate uses equals XLA's
    fp32 -> bf16 -> fp32 convert for every non-NaN value."""
    x = jnp.asarray(_float_cases(kind))
    got = np.asarray(bf16_round(x)).view(np.uint32)
    want = np.asarray(x.astype(jnp.bfloat16).astype(jnp.float32))
    np.testing.assert_array_equal(got, want.view(np.uint32))


def test_bisect_select_is_the_bf16_compare():
    rng = np.random.default_rng(1)
    s = jnp.asarray(rng.standard_normal((64, 256)), jnp.float32)
    t = jnp.asarray(rng.standard_normal((64, 1)), jnp.float32)
    want = s.astype(jnp.bfloat16) >= t.astype(jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(bisect_select(s, t)),
                                  np.asarray(want))
    # bf16 inputs pass through unchanged
    sb = s.astype(jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(bf16_round(sb)),
                                  np.asarray(sb.astype(jnp.float32)))
