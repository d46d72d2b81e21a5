"""Device→host reads per serving-loop step: ``serve()``'s ``pulls``
over its ``steps``, summed over the window's jobs (program counter)."""
import spans


def read(run):
    return spans.pulls_per_step(run)
