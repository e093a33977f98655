"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's XLA op intervals) / window, averaged over the
chips used, in %."""


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    return 100.0 * run.trace.idle_share()
