"""device_idle_pct: 100 x (1 - busy / window), busy being the union of
the intervals in which an operation ran on the device in the traced
window."""


def read(run):
    if run.device is None:
        return None
    return run.device.idle_pct
