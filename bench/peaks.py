"""Published peaks of each accelerator, keyed by ``device_kind`` as JAX
reports it.  A device that is not here is an error, never a default."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": per chip
    "TPU v5 lite": {"bf16_flop_s": 197e12, "hbm_byte_s": 819e9,
                    "hbm_bytes": 16e9, "source": "Google Cloud, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"have {sorted(PEAKS)}")
    return PEAKS[device_kind]
