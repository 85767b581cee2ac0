"""1 - union of the device's operation intervals over the traced window."""


def read(ctx, spec):
    busy, window = ctx["reduced"]["busy_s"], ctx["reduced"]["window_s"]
    if not window or not busy:
        return None
    return 100.0 * (1.0 - busy / window)
