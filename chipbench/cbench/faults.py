"""Faults planted in the timed path, each of which the check has to fail.

Each wraps the program's public ``LM.decode_step``; plant one before the
session is built (``ServeSession`` jits the method it finds then). Used by
``chipbench/tests/test_check.py`` at smoke size and by
``chipbench/calibrate.py --fault`` at a cell's own size on the chip.
"""
from __future__ import annotations


def _stale_cache(orig):
    """The decode step returns its cache unchanged: the state never moves."""
    def step(self, params, tokens, position, cache, **kw):
        logits, _ = orig(self, params, tokens, position, cache, **kw)
        return logits, cache
    return step


def _token_altered(orig):
    """Every decoded token is altered where it is produced."""
    import jax.numpy as jnp

    def step(self, *a, **kw):
        logits, cache = orig(self, *a, **kw)
        return jnp.roll(logits, 1, axis=-1), cache
    return step


FAULTS = {"stale-cache": _stale_cache, "token-altered": _token_altered}


def faulty_decode_step(name: str):
    """``LM.decode_step`` with the fault ``name`` planted in it."""
    from repro.models.transformer import LM
    return FAULTS[name](LM.decode_step)
