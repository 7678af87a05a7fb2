"""Share of the traced serving window in which no operation ran on the
device (the union of the device's operation events, from the trace)."""


def read(run):
    t = run.trace_data
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
