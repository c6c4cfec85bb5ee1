"""Mean time of the topology build of each refit (the ``fit.build`` span:
``psvgp.build``'s neighbour tables) in the traced window, in ms."""


def read(run):
    from harness import spans

    return spans.mean_ms(run, "fit.build")
