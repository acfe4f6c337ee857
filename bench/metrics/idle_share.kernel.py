"""Device: the largest, over the ranks, share of the traced window in
which the rank ran no operation, in percent."""


def read(rec):
    red = rec.trace
    return max(100.0 * (1.0 - red.busy_s(d) / red.window_s)
               for d in red.devices)
