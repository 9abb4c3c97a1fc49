"""Idle share of the busiest device over the traced part of the window:
1 - the union of its device-operation intervals over the traced span."""


def read(run):
    t = run["trace"]
    if not t or not t["busy_s_by_device"] or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - max(t["busy_s_by_device"].values()) /
                    t["window_s"])
