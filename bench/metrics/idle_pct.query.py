"""idle_pct.query: share of the profiler window in which no op ran on
the device (averaged over the chips used), in a query cell."""


def read(run):
    view = run.trace
    if view is None or not view.devices:
        return None
    return 100.0 * (1.0 - view.busy_s() / view.window_s)
