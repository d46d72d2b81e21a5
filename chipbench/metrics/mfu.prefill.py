"""Model FLOP/s utilization of the prefill: the window's requests per
second over whole jobs (host clock) times one prompt's prefill FLOPs
(matmuls and causal attention: ``prefill_flops`` of the configuration's
reference module, ``Run.ref``) over the chip's peak bf16 FLOP/s."""
import flops


def read(run):
    return flops.prefill_mfu(run)
