"""Mean device time per SGD step (``core.psvgp.train_step_gather``) in
the traced window, in ms."""


def read(run):
    from harness.trace import program_ms

    return program_ms(run.reduced, "train_step_gather")
