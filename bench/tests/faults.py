"""Faults planted in the timed path, for the tests that see ``correct``
come out false: each wraps the program's paged decode-step factory as
the server imports it."""
from __future__ import annotations

import jax.numpy as jnp


def _wrap(orig, alter):
    def factory(cfg, **kw):
        step = orig(cfg, **kw)

        def faulty(params, cache, token, position, kv_len, block_table):
            ntok, logits, new = step(params, cache, token, position, kv_len,
                                     block_table)
            return alter(ntok, logits, cache, new, cfg)
        return faulty
    return factory


def state_unchanged(ntok, logits, cache, new, cfg):
    """The step returns the cache it was given: no key, value or SSM
    state of the decoded token is kept."""
    return ntok, logits, cache


def half_batch(ntok, logits, cache, new, cfg):
    """Half of the slots, the odd ones, are left out: their rows emit
    token 0."""
    keep = jnp.arange(ntok.shape[0]) % 2 == 0
    return jnp.where(keep, ntok, 0), logits, new


def token_altered(ntok, logits, cache, new, cfg):
    """Each emitted token is replaced by its neighbour id."""
    return (ntok + 1) % cfg.vocab_size, logits, new


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "token_altered": token_altered}


def plant(monkeypatch, name: str) -> None:
    from repro.serve import server
    monkeypatch.setattr(server, "make_paged_decode_step",
                        _wrap(server.make_paged_decode_step, FAULTS[name]))
