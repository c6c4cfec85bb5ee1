"""Mean host time the front door takes to dispatch one coalesced batch
(the ``frontdoor.dispatch`` span: coalesce, route, move to the device and
launch ``_blend_eval``) in the traced window, in ms."""


def read(run):
    from harness import spans

    return spans.mean_ms(run, "frontdoor.dispatch")
