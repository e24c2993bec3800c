"""Published peaks of the chips the benchmark may run on, keyed by the
`device_kind` JAX reports. A device that is not here is an error, never a
default: a roofline share over an assumed peak means nothing."""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB HBM2e at 819 GB/s per chip.
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' system architecture",
    },
}
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]  # the same chip under its other name


class UnknownDevice(KeyError):
    pass


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; add them "
            f"to benchmark/peaks.py with their source") from None


def roofline_share_pct(flops: float, bytes_moved: float, seconds: float,
                       peaks: dict) -> tuple[float | None, str]:
    """The least time the chip could take for this work (the larger of
    operations over peak FLOP/s and bytes over peak bytes/s) as a percentage
    of the time it took, and which of the two bounds it."""
    t_compute = flops / peaks["bf16_flops_per_s"]
    t_memory = bytes_moved / peaks["hbm_bytes_per_s"]
    bound = "compute" if t_compute >= t_memory else "memory"
    if seconds <= 0:
        return None, bound
    return 100.0 * max(t_compute, t_memory) / seconds, bound
