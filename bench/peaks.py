"""Published peaks of the chips the benchmark runs on, and the bytes each
deque kernel needs per call.

Peaks, keyed by JAX's `device_kind`. Source: Google Cloud documentation,
"TPU v5e" (per chip: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at
819 GB/s). A device that is not in the table is an error, never a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
                    "int8_ops_per_s": 393e12, "hbm_bytes": 16e9},
}

INT32_BYTES = 4


def peak(device_kind: str) -> dict:
    """The peak table's row for `device_kind`; raises KeyError if absent."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add a row to bench/peaks.py "
                       f"with its source") from None


# Bytes are counted as the work each operation needs at the call's shapes,
# not the bytes the current implementation sweeps. `deque_apply` today walks
# the whole ring block once per push lane, and `steal_compact` reads the
# whole ring to pick a bottom window; counting those sweeps would credit a
# kernel for wasted traffic and let a better kernel, which moves less, read
# above 100%. Both kernels are integer kernels, and the peak table holds no
# integer-op peak that applies to them, so their roofline share is bounded
# by HBM bandwidth alone. Under a sweep's `vmap` one call serves every grid
# point on its chip, so a call needs `points` times one point's bytes.

def steal_compact_bytes(workers: int, grant_width: int, width: int,
                        points: int = 1) -> int:
    """One `steal_compact` call: the `grant_width` x `width` int32 records
    it exports per worker, plus the per-worker cursors it reads (bottom,
    size, grants) and writes (bottom, size), for each of `points`."""
    return INT32_BYTES * points * workers * (grant_width * width + 5)


def deque_apply_bytes(workers: int, lanes: int, width: int,
                      points: int = 1) -> int:
    """One `deque_apply` call: the `lanes` x `width` int32 records pushed per
    worker, plus their slot vector and the per-worker lane count, for each
    of `points`. `lanes` is the push log a tick needs on the common path
    (expansion children and the thief's loot), not the lanes reserved for
    rare transplants."""
    return INT32_BYTES * points * workers * (lanes * width + lanes + 1)


def roofline_share(needed_bytes: float, device_s: float,
                   device_kind: str) -> float | None:
    """Per cent of the HBM-bandwidth bound: (bytes / peak bandwidth) over
    the measured device time. None when the kernel never ran."""
    if device_s <= 0 or needed_bytes <= 0:
        return None
    bound_s = needed_bytes / peak(device_kind)["hbm_bytes_per_s"]
    return 100.0 * bound_s / device_s
