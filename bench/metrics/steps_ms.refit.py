"""Mean time of each refit's SGD steps, dispatch and device work until the
parameters are ready (the ``fit.steps`` span around ``psvgp.fit``) in the
traced window, in ms."""


def read(run):
    from harness import spans

    return spans.mean_ms(run, "fit.steps")
