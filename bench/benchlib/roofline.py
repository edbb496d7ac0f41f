"""Operations and bytes of the serving path's gather-distance kernel, from
the work the search asks for, and its share of the chip's roofline.

One distance computation reads the candidate's row and its id: ``4 d``
bytes for an f32 row, ``d`` for an int8 row, and 4 for the id.  The
norm and the int8 scale of the candidate are read by the norm expansion
that follows the kernel, not by the kernel, so they are not counted
against its time.  It computes ``2 d`` operations (a multiply and an
add per coordinate).  The count is algorithmic: a kernel that reads a
whole padded tile per candidate, as the int8 kernel does, is slower
against it, not measured on another yardstick.
"""
from __future__ import annotations

ROW_BYTES_PER_COORD = {"f32": 4, "int8": 1}
ID_BYTES = 4


def gather_work(dist_comps: int, d: int, packing: str) -> tuple[float, float]:
    """(operations, bytes) of ``dist_comps`` distance computations."""
    row = ROW_BYTES_PER_COORD[packing] * d + ID_BYTES
    return 2.0 * d * dist_comps, float(row) * dist_comps


def least_seconds(ops: float, nbytes: float, peak: dict, packing: str
                  ) -> tuple[float, str]:
    """The least time the chip needs for the work, and which bound sets it
    ("compute" or "memory").  f32 products are held to the bf16 MXU peak,
    the highest rate the chip has for them."""
    rate = peak["int8_ops_per_s"] if packing == "int8" else \
        peak["bf16_flops_per_s"]
    t_ops, t_mem = ops / rate, nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


def roofline_share(dist_comps: int, d: int, packing: str,
                   kernel_seconds: float, peak: dict) -> float | None:
    """Percent of the roofline the kernel reached; None without kernel
    time to divide by."""
    if kernel_seconds <= 0 or dist_comps <= 0:
        return None
    ops, nbytes = gather_work(dist_comps, d, packing)
    least, _ = least_seconds(ops, nbytes, peak, packing)
    return 100.0 * least / kernel_seconds
