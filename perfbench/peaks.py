"""Published peaks of the chips the benchmark knows, keyed by the
`device_kind` JAX reports. A device that is not here is an error, never a
default."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": HBM at 819 GB/s per chip
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peak(device_kind: str, what: str) -> float:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in perfbench/peaks.py (has: {sorted(PEAKS)})")
    return PEAKS[device_kind][what]
