"""Roofline share of the blend program in the traced window: the least
time the chip could take for the work (the larger of FLOPs over peak
FLOP/s and bytes over peak bytes/s, counted from shapes in
``harness/work.py``) over the program's device time, in percent. At
these shapes the bytes bound it: the cached factors are read once per
batch and the FLOPs per byte are far below the chip's ratio."""
import sys


def read(run):
    from harness import work

    count, seconds = run.reduced["programs"].get("_blend_eval", (0, 0.0))
    fd = run.counters.get("frontdoor", {}).get("batches", {})
    if not count or not seconds or fd.get("count") != count:
        return None
    m = int(run.cfg["num_inducing"])
    P = int(run.cfg["grid"][0]) * int(run.cfg["grid"][1])
    rows = fd["rows_total"]
    flops = rows * work.blend_point_flops(m)
    nbytes = work.blend_bytes(rows, count, P, m)
    t_flops = flops / run.peak["flops_per_s"]
    t_bytes = nbytes / run.peak["bytes_per_s"]
    bound = "bytes" if t_bytes >= t_flops else "flops"
    print(f"blend_roofline.bulk: bound by {bound}", file=sys.stderr)
    return 100.0 * max(t_flops, t_bytes) / seconds
