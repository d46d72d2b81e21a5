"""Seconds from process start to the first measured job: imports, the
device, the weights and the warm-up job (host clock)."""


def read(run):
    return run.setup_s
