"""Faults planted under the timed path, for the tests that show the
check catches them.  Each wraps the program's decode step
(``repro.models.decode.serve_step``), which ``serve()`` traces anew in
every call, so patching the module attribute reaches the window's jobs.

The faults a served cell can have: a step that returns its state
unchanged; half of the batch left out (its slots answered with the
other half's logits); a token altered where it is produced.  An
exchange between chips does not exist on one chip."""
from __future__ import annotations

import jax.numpy as jnp


def state_unchanged(step):
    def f(params, cfg, cache, tokens, pos):
        logits, _ = step(params, cfg, cache, tokens, pos)
        return logits, cache
    return f


def half_batch(step):
    def f(params, cfg, cache, tokens, pos):
        logits, cache = step(params, cfg, cache, tokens, pos)
        b = logits.shape[0]
        h = b // 2
        return logits.at[h:].set(logits[:b - h]), cache
    return f


def token_altered(step):
    def f(params, cfg, cache, tokens, pos):
        logits, cache = step(params, cfg, cache, tokens, pos)
        return jnp.roll(logits, 1, axis=-1), cache
    return f


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "token_altered": token_altered}
