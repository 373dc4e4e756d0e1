"""Published peaks of one chip, keyed by JAX's ``device_kind``, and the
operations and bytes of the kernels whose roofline share is reported.

Source of the v5e numbers: Google Cloud documentation, "TPU v5e" (system
architecture): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
No float32 MXU peak is published; float32 contractions are measured
against the bf16 peak, so a kernel that takes float32 in three bf16 passes
can reach about a third of it, and in six about a sixth.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a kind not in the table is an error."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r};"
                       f" the table has {sorted(PEAKS)}")
    return PEAKS[device_kind]


def roofline_s(flops: float, nbytes: float, device_kind: str) -> float:
    """The least time the chip could take: the larger of the operations at
    the bf16 peak and the bytes at the HBM bandwidth."""
    p = peaks(device_kind)
    return max(flops / p["bf16_flops"], nbytes / p["hbm_bytes_per_s"])


def gram_flops(n: int, m: int) -> float:
    """``x' x`` of a stored ``(n, m)`` operand: ``2 n m m``."""
    return 2.0 * n * m * m


def gram_bytes(n: int, m: int, itemsize: int = 4) -> float:
    """The Gram kernel's operands (``x`` as A and as B, each read once)
    and its ``(m, m)`` result."""
    return float(itemsize) * (2 * n * m + m * m)


def bcoo_bytes(stored: int, index_dims: int = 2, itemsize: int = 4) -> float:
    """One read of a BCOO operand's ``stored`` slots: the data and the
    int32 indices."""
    return float(stored) * (itemsize + 4 * index_dims)
