"""Published peaks of the chips a cell may run on, keyed by JAX's
`device_kind`. A device that is not here is an error, never a default.

Copied from `paddle_tpu/profiler/perf_attribution.DEFAULT_PEAK_TABLE` (the
original is listed in PERF.md for a later PR to delete). Source: Google
Cloud documentation, "TPU v5e" system architecture page: 197 TFLOP/s bf16,
393 TOP/s int8, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s chip-to-chip.
"""

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bytes_per_s": 200e9,
        "source": "cloud.google.com/tpu/docs/v5e",
    },
}
ALIASES = {"TPU v5e": "TPU v5 lite"}


class UnknownDevice(LookupError):
    pass


def peak_for(device_kind: str) -> dict:
    key = device_kind if device_kind in PEAKS else ALIASES.get(device_kind)
    if key is None:
        raise UnknownDevice(
            f"no published peak for device kind {device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[key]
