"""Model FLOP/s utilization of the decode step (device trace): one
step's model FLOPs for every busy slot (matmuls, unembedding, and
attention over the live context: ``decode_step_flops`` of the
configuration's reference module, ``Run.ref``) over the
start-to-start period of the decode steps inside the traced window,
over the chip's peak bf16 FLOP/s.  Prefill work is not counted."""
import flops
import xtrace


def read(run):
    if run.trace is None:
        return None
    return flops.decode_mfu(run, xtrace.decode_period_s(run.trace))
