"""Device: the share of the summed job time in which no operation ran on
the chip, from the profiler trace, mean over the cell's chips, in %."""


def read(w):
    s = w.trace
    if s is None or not s.window_ns:
        return None
    return 100.0 * (1.0 - s.busy_s / s.window_s)
