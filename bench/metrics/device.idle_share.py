"""Share of the traced window, in %, in which no operation ran on the
chip: 1 - the union of the device's op intervals over the window's length
(``bench/trace_reduce.py``).  Layer: device."""


def read(run):
    if run.trace is None or run.trace.window_s <= 0.0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
