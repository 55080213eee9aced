"""Published peaks, keyed by ``jax.devices()[0].device_kind``.

A device that is not in the table is an error, never a default.
"""

PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 16 GB of HBM2e "
                  "at 819 GB/s per chip",
    },
}


def peak(device_kind: str, key: str) -> float:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}:"
                       f" add it to chipbench/peaks.py with its source")
    return PEAKS[device_kind][key]
