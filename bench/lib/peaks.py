"""Published peaks per chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" page
(https://cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 393 TOP/s int8,
16 GB HBM at 819 GB/s, 1,600 Gbit/s inter-chip interconnect per chip.
No fp32 peak is published; fp32 contractions are charged against the bf16
peak, so a share of it can never pass 100%.  A kind not listed here is an
error: the yardstick never guesses a chip.
"""
from __future__ import annotations

SOURCE = ("Google Cloud documentation, 'TPU v5e' page, "
          "https://cloud.google.com/tpu/docs/v5e")

_V5E = {"bf16_flop_s": 197e12, "int8_op_s": 393e12, "hbm_bytes": 16e9,
        "hbm_bytes_s": 819e9, "ici_bits_s": 1.6e12, "source": SOURCE}

PEAKS = {
    "TPU v5 lite": _V5E,          # what JAX reports for a v5e chip
    "TPU v5e": _V5E,
}


class UnknownDevice(KeyError):
    pass


def peaks(kind: str) -> dict:
    try:
        return PEAKS[kind]
    except KeyError:
        raise UnknownDevice(f"no published peaks for device kind {kind!r}; "
                            f"known: {sorted(PEAKS)}") from None
