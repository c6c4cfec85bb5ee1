"""Mean time of the posterior cache build after each refit (the
``fit.cache`` span: ``psvgp.posterior_cache`` until its factors are
ready) in the traced window, in ms."""


def read(run):
    from harness import spans

    return spans.mean_ms(run, "fit.cache")
