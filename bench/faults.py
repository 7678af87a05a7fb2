"""Faults planted in the timed path, for the tests and the calibration
that show each comparison fails them. The benchmark's runs plant none."""
from __future__ import annotations

import jax


def unchanged_state(step):
    """A step that computes its loss but returns its state unchanged."""
    return jax.jit(lambda p, o, b: (p, o, step(p, o, b)[2]))


def half_batch(step):
    """The loss is the mean over the first half of the rows (over the
    first half of the positions, for a batch of one)."""
    def broken(p, o, b):
        m = b["mask"]
        if m.shape[0] > 1:
            m = m.at[m.shape[0] // 2:].set(False)
        else:
            m = m.at[:, m.shape[1] // 2:].set(False)
        return step(p, o, dict(b, mask=m))
    return jax.jit(broken, donate_argnums=(0, 1))


def altered_token(engine):
    """The decode step hands back a wrong token for slot 0."""
    step = engine._step_fn
    vocab = engine.model.cfg.vocab_size

    def broken(*args):
        outs = step(*args)
        nxt = outs[0]
        return (nxt.at[0].set((nxt[0] + 1) % vocab),) + tuple(outs[1:])
    engine._step_fn = broken
