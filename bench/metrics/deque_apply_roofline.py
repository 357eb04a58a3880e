"""deque_apply_roofline: per cent of the HBM-bandwidth bound that the
`deque_apply` kernel reached in the profiled window: the bytes its calls
need at the call's shapes and grid points
(`bench.peaks.deque_apply_bytes`) over peak bandwidth, divided by the
device time of its events. An integer kernel: bounded by bandwidth alone.
Absent when the trace holds no call of it."""

from bench import peaks


def read(run):
    k = (run.trace or {}).get("kernels", {}).get("deque_apply")
    if not k or not k["count"]:
        return None
    s = run.shapes
    need = k["count"] * peaks.deque_apply_bytes(
        s["workers"], s["lanes"], s["width"], s["points"])
    return peaks.roofline_share(need, k["device_s"], run.device_kind)
