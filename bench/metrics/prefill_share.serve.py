"""Device time of the engine's prefill programs (jitted programs named
after ``prefill``) over all device busy time in the traced window."""


def read(run):
    t = run.trace_data
    if not t or t["busy_s"] <= 0:
        return None
    pre = sum(v for k, v in t["module_time"].items() if "prefill" in k)
    if pre <= 0:
        return None
    return 100.0 * pre / (t["busy_s"] * len(run.devices))
