"""Milliseconds from one decode step's start to the next on the device,
over the decode steps that ran wholly inside the traced window (device
trace).  Taken between the first and last step's starts, so a step cut
by the window's edge does not count as one."""
import xtrace


def read(run):
    if run.trace is None:
        return None
    p = xtrace.decode_period_s(run.trace)
    return None if p is None else p * 1e3
