"""Mean milliseconds of a ``serve()`` call's set-up, its ``serve.setup``
span (cache, programs, allocator, warm step), over the window's jobs
(program counter)."""
import spans


def read(run):
    return spans.call_setup_ms(run)
