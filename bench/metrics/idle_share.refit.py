"""Share of the traced window in which no operation ran on the chip:
1 - (union of device op intervals) / window, in percent."""


def read(run):
    r = run.reduced
    return 100.0 * (1.0 - r["busy_s"] / r["window_s"]) if r["window_s"] > 0 else None
