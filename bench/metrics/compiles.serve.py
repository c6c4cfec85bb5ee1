"""Programs jax compiled or loaded from its cache inside the window
(backend-compile events, ``harness/clock.py``). Set-up warms every batch
shape, so a count above 0 means the window met a shape it did not."""


def read(run):
    return run.counters.get("compiles_in_window")
