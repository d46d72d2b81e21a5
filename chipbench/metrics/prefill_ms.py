"""Mean device milliseconds of one prefill execution, over the prefills
that ran wholly inside the traced window (device trace)."""
import xtrace


def read(run):
    if run.trace is None:
        return None
    runs = xtrace.program_runs(run.trace)[1]
    if not runs:
        return None
    return sum(e - s for _, s, e in runs) / len(runs) * 1e-6
