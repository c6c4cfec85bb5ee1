"""Faults planted in the program underneath the timed path, for the
tests that see ``correct`` come out false and for ``calibrate.py``'s
fault readings. Each is a context manager that patches the program's
module and restores it, clearing jax's caches so that no compiled
program of the other kind is reused."""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def _patched(module, name, value):
    import jax

    old = getattr(module, name)
    jax.clear_caches()
    setattr(module, name, value)
    try:
        yield
    finally:
        setattr(module, name, old)
        jax.clear_caches()


def state_unchanged():
    """Every SGD step hands back the state it was given."""
    import jax.numpy as jnp

    from repro.core import psvgp

    return _patched(psvgp, "train_step_gather", lambda state, *a, **k: (state, jnp.zeros(())))


def half_batch():
    """Half of every mini-batch is masked out; the ELBO's mean runs over
    the rest."""
    from repro.core import psvgp

    real = psvgp.gather_minibatch

    def gather(*args, **kwargs):
        bx, by, bm = real(*args, **kwargs)
        return bx, by, bm.at[:, bm.shape[1] // 2:].set(0.0)

    return _patched(psvgp, "gather_minibatch", gather)


def answer_altered():
    """The first answer of every served batch is moved by 1% of the
    largest mean in the batch."""
    import jax.numpy as jnp

    from repro.core import blend

    real = blend._blend_eval

    def blend_eval(*args, **kwargs):
        mean, var = real(*args, **kwargs)
        return mean.at[0].add(0.01 * jnp.max(jnp.abs(mean)) + 1e-3), var

    return _patched(blend, "_blend_eval", blend_eval)


PLANTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "answer_altered": answer_altered}
