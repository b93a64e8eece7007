"""Faults a cell's timed path can have, planted in the program from the
outside; the comparison that decides ``correct`` must catch each. Used
by bench/calibrate.py (readings on the chip) and bench/tests. A training
fault is ``fault(sess, arch)``, a serving fault ``fault(engine)``."""

from __future__ import annotations


def unchanged_state(sess, arch):
    """A step that returns params and optimizer state unchanged."""
    sess.opt_step = lambda params, grads, opt: (params, opt, {})


def half_batch(sess, arch):
    """Half of the batch left out, the mean taken over the rest (the
    kept rows twice, so the shapes stay)."""
    import jax.numpy as jnp

    inner = sess.train_step

    def step(params, batch):
        return inner(params, {k: jnp.concatenate([v[: len(v) // 2]] * 2)
                              for k, v in batch.items()})

    sess.train_step = step


def grad_altered(sess, arch):
    """One leaf's gradient doubled where the train step produces it: the
    architecture's ``FAULT_LEAF`` in the first layer slot that holds it."""
    inner = sess.train_step

    def step(params, batch):
        grads, metrics = inner(params, batch)
        seg, slot = next((seg, slot) for seg in grads["segments"].values()
                         for slot in sorted(seg, key=_slot_order)
                         if slot.split(".", 1)[1] == arch.FAULT_LEAF)
        seg[slot] = seg[slot] * 2
        return grads, metrics

    sess.train_step = step


def _slot_order(full: str) -> int:
    """The slot of a layer leaf: 3 for "L3.<name>"."""
    return int(full.split(".", 1)[0][1:])


def token_altered(eng):
    """Each request's fifth served token replaced where the engine
    produces it."""
    emit = eng._emit

    def altered(req, tok):
        if len(req.tokens) == 4:
            tok = (tok + 1) % eng.session.cfg.vocab
        emit(req, tok)

    eng._emit = altered


TRAIN = (unchanged_state, half_batch, grad_altered)
SERVE = (token_altered,)
