"""A cell small enough for the CPU (Pallas kernels in interpret mode):
the harness's whole run, minus its look for a chip."""
from __future__ import annotations

import copy

import run

# float32, so the program's own rounding leaves the served tokens at
# the reference's best (over seeds 1-5 widest gaps 0 to 0.0086, mean
# gaps 0 to 0.00027) and both fp8 controls (widest 0.095 to 0.184, mean
# 0.010 to 0.026) and every planted fault (widest 0.27 and more, mean
# 0.038 and more) stand clear of the limits
MODEL = {
    "name": "tiny", "family": "dense", "n_layers": 2, "d_model": 128,
    "n_heads": 4, "n_kv_heads": 2, "head_dim": 32, "d_ff": 256,
    "vocab_size": 512, "qk_norm": True, "rope_theta": 10000.0,
    "norm_type": "rmsnorm", "mlp_variant": "swiglu", "tie_embeddings": True,
    "attention_variant": "topk", "topk_k": 16, "dtype": "float32",
}

SPEC = {
    "name": "tiny.decode", "chips": 1,
    "config": {
        "reference": "refs/dense.py",
        "model": MODEL, "program_fixed": {"norm_eps": 1e-6},
        "serving": {"kv": {"layout": "paged", "page_size": 16},
                    "sata": {"decode": {"mode": "on", "block": 16}}},
    },
    "traffic": {"slots": 2, "prompt_len": 40, "gen_len": 8,
                "requests_per_job": 4, "warmup_gen_len": 2},
    "cell": {"trace_start_s": 0.0, "trace_seconds": 0.1,
             "check_requests": 4, "max_logit_gap": 0.03,
             "mean_logit_gap": 0.003},
    "end_to_end": [{"name": "out_tok_s", "unit": "tokens/s"},
                   {"name": "setup_s", "unit": "s"}],
    "per_layer": [{"name": "plan_tile_share", "unit": "%"},
                  {"name": "mfu.decode", "unit": "%"}],
}

PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def spec(reference: str = "", **cell):
    """The tiny cell, with its reference module loaded as ``load_spec``
    loads a configuration's; ``reference`` (a path under
    ``chipbench/``) names another module in its place."""
    s = copy.deepcopy(SPEC)
    s["cell"].update(cell)
    if reference:
        s["config"]["reference"] = reference
    s["reference"] = run.load_reference(s["config"]["reference"])
    return s
