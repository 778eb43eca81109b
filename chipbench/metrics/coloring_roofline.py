"""The whole coloring's share of its roofline, in percent: the least time of
one coloring's work on the cell's chips (``work.coloring_work``, the larger of
operations over peak FLOP/s and bytes over peak HBM bandwidth) over the device's
busy time per coloring in the traced window."""

import work


def read(run):
    red, peak = run["trace"], run["peak"]
    if not red or not peak or not run["colorings"]:
        return None
    ops, nbytes = run["work"]
    least, bound = work.least_seconds(ops, nbytes, peak, run["chips"])
    busy = sum(red["busy_s"]) / len(red["busy_s"])
    if busy <= 0:
        return None
    share = 100.0 * least / (busy / run["colorings"])
    print(f"[roofline] least {least:.6g} s per coloring, bound by {bound} "
          f"({ops:.6g} ops, {nbytes:.6g} bytes); busy {busy / run['colorings']:.6g} s "
          f"per coloring: {share:.6g}%", flush=True)
    return share
