"""Mean host time of the copy of one batch's answers from the device to
the host, after the device finished it (the ``server.fetch`` span of the
replicated server's collect stage) in the traced window, in ms."""


def read(run):
    from harness import spans

    return spans.mean_ms(run, "server.fetch")
