"""steal_compact_roofline: per cent of the HBM-bandwidth bound that the
`steal_compact` kernel reached in the profiled window: the bytes its calls
need at the call's shapes and grid points
(`bench.peaks.steal_compact_bytes`) over peak bandwidth, divided by
the device time of its events. An integer kernel: bounded by bandwidth
alone. Absent when the trace holds no call of it."""

from bench import peaks


def read(run):
    k = (run.trace or {}).get("kernels", {}).get("steal_compact")
    if not k or not k["count"]:
        return None
    s = run.shapes
    need = k["count"] * peaks.steal_compact_bytes(
        s["workers"], s["grant_width"], s["width"], s["points"])
    return peaks.roofline_share(need, k["device_s"], run.device_kind)
