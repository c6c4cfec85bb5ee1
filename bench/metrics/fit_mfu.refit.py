"""The whole fit's share of the chip's peak FLOP/s: the algorithmic FLOPs
of the ELBO's forward and backward pass (backward counted as twice the
forward) and the Adam update, per SGD step of every partition
(``harness/work.py``), times the steps in the window, over window x
peak, in percent. Cache builds and data preparation are not counted."""


def read(run):
    from harness import work

    steps = run.counters.get("steps")
    if not steps or run.window_s <= 0:
        return None
    P = int(run.cfg["grid"][0]) * int(run.cfg["grid"][1])
    flops = steps * work.sgd_step_flops(P, int(run.cfg["batch_size"]), int(run.cfg["num_inducing"]))
    return 100.0 * flops / (run.window_s * run.peak["flops_per_s"])
