"""Device: one minus the union of device-operation intervals over the
traced window; on several chips, the mean over them (each chip is on an
earlier line)."""


def read(run):
    if run.trace is None:
        return None
    idle = run.trace.idle_share
    return 100.0 * sum(idle.values()) / len(idle)
