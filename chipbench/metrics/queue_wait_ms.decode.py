"""Mean milliseconds from the ``serve()`` call to a request's claim,
over every request of the window's jobs (``serve()``'s
``queue_wait_s``; program counter)."""
import spans


def read(run):
    return spans.queue_wait_ms(run)
