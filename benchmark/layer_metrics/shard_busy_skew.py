"""How unevenly the mesh's devices are busy over the traced part of the
window: (the most - the least) busy seconds over the most, from the
trace reduction's `busy_s_by_device`. 0 where every shard does the same
work; a device that also does single-chip work for the others (a
fragment routed off the mesh runs on device 0) shows here. A trace of
fewer than two device planes has no skew to read."""


def read(run):
    t = run["trace"]
    if not t or len(t["busy_s_by_device"]) < 2:
        return None
    busy = t["busy_s_by_device"].values()
    if max(busy) <= 0:
        return None
    return 100.0 * (max(busy) - min(busy)) / max(busy)
