"""Generated tokens of all window jobs over their summed wall time
(host clock; each job timed from ``serve()`` call to return)."""


def read(run):
    return run.rate("generated_tokens")
