"""Mean device time per execution of the replicated blend program
(``core.blend._blend_eval``) in the traced window, in ms."""


def read(run):
    from harness.trace import program_ms

    return program_ms(run.reduced, "_blend_eval")
