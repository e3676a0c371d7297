"""A traversal program's share of its roofline, in percent.

Work: the traversal bytes and operations (counts.py) of the traced
window's answers, over the window's length, is the rate of work. Time:
the device seconds of the program's modules in the trace over the traced
window's length is the rate at which the device spent time on them. The
share is the least time the chip could take for the work over the time it
took, both per second of the window. The bound that applies is noted.
Nothing is read, and a note says why, where the backend does not count
hops or distance evaluations."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import counts  # noqa: E402


def share(ctx, name: str, modules: tuple[str, ...]):
    t = ctx.trace
    if t is None or not ctx.stats["queries"]:
        return None
    calcs = ctx.counter("dist_calcs", name)
    hops = ctx.counter("hops", name)
    if calcs is None or hops is None:       # the work cannot be counted
        return None
    dev_s = sum(t["modules"][m]["seconds"] for m in modules
                if m in t["modules"])
    if dev_s <= 0 or t["window_s"] <= 0:
        return None
    n_bytes = counts.traversal_bytes(calcs, hops, ctx.shape["row_bytes"],
                                     ctx.shape["nbr_list_bytes"])
    n_ops = counts.traversal_ops(calcs, ctx.shape["dim"])
    least, bound = counts.least_time(n_bytes, n_ops, ctx.peak)
    layer_s = ctx.layer[1] - ctx.layer[0]
    ctx.note(f"{name}: bound={bound} bytes={n_bytes} ops={n_ops} "
             f"least_s={least} layer_s={layer_s} device_s={dev_s} "
             f"trace_window_s={t['window_s']}")
    return 100.0 * (least / layer_s) / (dev_s / t["window_s"])
