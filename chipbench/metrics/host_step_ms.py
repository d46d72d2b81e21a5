"""Device-idle milliseconds per decode step in which the host was inside
a ``serve.*`` span other than ``serve.pull``: Python bookkeeping, fetch
accounting, table pushes and dispatch (device trace, over the decode
steps wholly inside the traced window; ``spans.idle_split``)."""
import spans


def read(run):
    if run.trace is None:
        return None
    split = spans.idle_split(run.trace)
    return None if split is None else split["host"]
