"""Published inter-chip rates of the chips the benchmark may run on,
keyed by the `device_kind` JAX reports, beside `peaks.py`'s memory
rates.  A device that is not here is an error, not a default.

Source: Google Cloud documentation, "TPU v5e": 1,600 Gbps of inter-chip
interconnect bandwidth a chip (200 GB/s, all its links together).
"""
ICI_PEAKS = {
    "TPU v5 lite": {"ici_bytes_per_s": 1600e9 / 8,
                    "source": "Google Cloud documentation, TPU v5e: "
                              "1,600 Gbps interchip interconnect a chip"},
}


def ici_peak_of(device_kind: str) -> dict:
    if device_kind not in ICI_PEAKS:
        raise KeyError(f"benchmark: no published inter-chip rate for "
                       f"device kind {device_kind!r}; the table has "
                       f"{sorted(ICI_PEAKS)}")
    return ICI_PEAKS[device_kind]
