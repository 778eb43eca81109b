"""Share of the traced window in which no operation ran on the device, in
percent, averaged over the cell's chips (union of the device's operation
intervals, ``trace_reduce.reduce_trace``)."""


def read(run):
    red = run["trace"]
    if not red or not red["busy_s"]:
        return None
    busy = sum(red["busy_s"]) / len(red["busy_s"])
    return 100.0 * (1.0 - busy / red["window_s"])
