"""A reference module for the test that the harness reaches a
configuration's module only through the path its configuration names:
the dense logits, with every call recorded, and fixed counts."""
from refs import dense

CONTROLS = dense.CONTROLS
DECODE_STEP_FLOPS = 10 ** 9
PREFILL_FLOPS = 3 * 10 ** 11

calls = []          # the ``low`` of every ``logits`` call, in order


def logits(params, model, tokens, out_pos, low=""):
    calls.append(low)
    return dense.logits(params, model, tokens, out_pos, low)


def prefill_flops(model, prompt_len):
    return PREFILL_FLOPS


def decode_step_flops(model, pos):
    return DECODE_STEP_FLOPS
