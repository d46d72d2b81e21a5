"""Device-idle milliseconds per decode step in which the host was inside
``serve.pull``, a device→host read (device trace, over the decode steps
wholly inside the traced window; ``spans.idle_split``)."""
import spans


def read(run):
    if run.trace is None:
        return None
    split = spans.idle_split(run.trace)
    return None if split is None else split["pull"]
