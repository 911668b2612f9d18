"""Published peaks of the chips the benchmark may run on, keyed by the
`device_kind` JAX reports.  A device that is not here is an error, not
a default, and nothing overrides the table.

Source: Google Cloud documentation, "TPU v5e": 819 GB/s of HBM
bandwidth a chip.
"""
PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks_of(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"benchmark: no published peaks for device kind "
                       f"{device_kind!r}; the table has {sorted(PEAKS)}")
    return PEAKS[device_kind]
