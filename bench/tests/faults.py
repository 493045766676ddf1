"""Faults planted underneath a cell's timed path, for the tests that see
``correct`` come out false.  Each is ``fault(ctx, state)``, applied by
``harness.run`` after set-up and before the window."""
import jax
import jax.numpy as jnp


def _wrap_logits(st, change):
    inner = st["decode"]

    def decode(*args):
        logits, cache = inner(*args)
        return change(logits), cache
    st["decode"] = decode


def token_altered(ctx, st):
    """The answers changed where they are produced: every row's logits
    rolled by one token."""
    _wrap_logits(st, lambda l: jnp.roll(l, 1, axis=-1))


def slot_altered(ctx, st):
    """One slot's answers changed where they are produced: the logits of
    the batch's second row rolled by one token, the others served as
    they are."""
    _wrap_logits(st, lambda l: l.at[1].set(jnp.roll(l[1], 1, axis=-1)))


def state_unchanged(ctx, st):
    """A decode step that hands back the cache it was given."""
    inner = st["decode"]

    def decode(params, tok, cache, pos, states):
        kept = jax.tree.map(jnp.copy, cache)
        logits, _ = inner(params, tok, cache, pos, states)
        return logits, kept
    st["decode"] = decode
