"""Mean host time of the re-partitioning of each refit (the
``fit.partition`` span: grid and padded partition storage of the new
slice) in the traced window, in ms."""


def read(run):
    from harness import spans

    return spans.mean_ms(run, "fit.partition")
