"""The whole serve step's share of the chip's peak FLOP/s: the
algorithmic FLOPs of every point answered in the window
(``harness/work.py``) over window x peak, in percent."""


def read(run):
    from harness import work

    points = run.counters.get("points")
    if not points or run.window_s <= 0:
        return None
    flops = points * work.blend_point_flops(int(run.cfg["num_inducing"]))
    return 100.0 * flops / (run.window_s * run.peak["flops_per_s"])
