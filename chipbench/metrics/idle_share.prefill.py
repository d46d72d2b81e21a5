"""Percent of the traced window in which no operation ran on the
device (device trace: one minus the union of operation intervals over
the window)."""
import xtrace


def read(run):
    return None if run.trace is None else xtrace.idle_share(run.trace)
