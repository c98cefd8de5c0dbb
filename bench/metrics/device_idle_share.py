"""Device: the share of the traced window in which no operation ran on the
chip, ``1 - busy / window``, from the profiler trace.  None without one."""


def read(w):
    if w.device is None or w.device.window_s() <= 0:
        return None
    return 100.0 * (1.0 - w.device.busy_s() / w.device.window_s())
