"""Published peaks of the chips the benchmark may run on, keyed by JAX's
``device_kind``. One table, so every utilization and roofline share divides
by the same number; a device that is not here is an error, not a default."""

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,        # FLOP/s, dense bf16
        "int8_ops": 393e12,          # OP/s
        "hbm_bytes_s": 819e9,        # bytes/s
        "hbm_bytes": 16e9,
        "ici_bits_s": 1600e9,
        "source": "Google Cloud documentation, 'TPU v5e' system architecture page",
    },
}


class UnknownDevice(KeyError):
    pass


def peaks_for(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(f"no published peaks on record for device kind "
                            f"{device_kind!r}; add a row to benchmarks/lib/peaks.py "
                            f"with its source") from None
