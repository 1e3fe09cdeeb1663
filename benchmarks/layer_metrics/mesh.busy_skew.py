"""mesh.busy_skew: how unevenly the devices of a mesh were busy.

Busy seconds per device plane (harness/trace.py `reduce`: the union of the
plane's op intervals) summed over the solo sub-windows of the metric's
classes; the busiest device over the mean. 1.0 is even; on a mesh of four
4.0 means one device did all the work. Nothing to read (None) where no solo
sub-window has a device plane.
"""


def read(obs, spec):
    busy: dict = {}
    for cls in spec.get("classes") or obs.get("solo", {}):
        per = (obs.get("solo", {}).get(cls) or {}).get("trace", {}) \
            .get("per_device") or {}
        for dev, s in per.items():
            busy[dev] = busy.get(dev, 0.0) + s
    if not busy or not sum(busy.values()):
        return None
    # a device of the cell that ran nothing has no plane: it counts as 0
    n = max(len(busy), obs.get("chips") or 0)
    return max(busy.values()) / (sum(busy.values()) / n)
