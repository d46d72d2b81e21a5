"""Quickstart: SATA end to end in ~60 seconds on CPU.

1. Build top-k selective masks for a KVT-like workload.
2. Run Algo 1 (sort+classify) + Algo 2 (FSM schedule) and print the
   Tab.-I statistics.
3. Simulate scheduled vs dense/gated execution (Fig. 4a).
4. Plan the TPU-native block-sparse execution and run the Pallas kernel
   (interpret mode) against the exact top-k oracle.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.workloads import WORKLOADS
from repro.core import (HwConfig, plan, simulate_dense, simulate_gated,
                        simulate_tiled_sata)
from repro.core.blockmap import block_skip_fraction
from repro.core.masks import SyntheticTrace, synthetic_masks, topk_mask
from repro.kernels.ops import sata_attention, sata_attention_reference
from repro.launch.compile_cache import use_compile_cache


def main():
    use_compile_cache()
    # --- 1-3: the paper's evaluation plane --------------------------------
    w = WORKLOADS["kvt_tiny"]
    masks = synthetic_masks(0, w.trace, w.n_heads)
    p = plan(masks, s_f=w.s_f)
    print(f"workload {w.name}: N={w.n_tokens} K={w.k} S_f={w.s_f}")
    print(f"  post-schedule stats: GlobQ%={p.stats.glob_q_frac:.3f} "
          f"(paper {w.paper_glob_q}), S_h={p.stats.avg_s_h_frac:.3f}N "
          f"(paper {w.paper_s_h_frac}N)")
    hw = HwConfig()
    r = simulate_tiled_sata(p.tiled, w.d_k, hw)
    d = simulate_dense(masks, w.d_k, hw)
    g = simulate_gated(masks, w.d_k, hw)
    print(f"  throughput gain vs dense: {r.throughput_gain(d):.2f}x "
          f"(paper {w.paper_throughput_gain}x)")
    print(f"  energy-eff gain vs dense: {r.energy_eff_gain(d):.2f}x "
          f"(paper {w.paper_energy_gain}x)")
    print(f"  gated baseline saves energy but not time: "
          f"{g.latency_cycles/d.latency_cycles:.2f}x latency, "
          f"{d.energy_pj/g.energy_pj:.2f}x energy")

    # --- 4: the TPU plane --------------------------------------------------
    tr = SyntheticTrace(n_tokens=256, k=32, cluster_scale=3.0,
                        discrete_clusters=8, noise=0.3)
    m = jnp.asarray(synthetic_masks(0, tr, n_heads=2))
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((2, 256, 64)), jnp.float32)
    k_ = jnp.asarray(rng.standard_normal((2, 256, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 256, 64)), jnp.float32)
    out, bm = sata_attention(q, k_, v, m, q_block=32, k_block=32)
    ref = sata_attention_reference(q, k_, v, m)
    print(f"pallas kernel: block skip {float(block_skip_fraction(bm)):.2%}, "
          f"max err vs exact top-k oracle "
          f"{float(jnp.max(jnp.abs(out - ref))):.2e}")


if __name__ == "__main__":
    main()
