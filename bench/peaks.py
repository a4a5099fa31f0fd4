"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` string JAX reports. A chip missing from the table is an
error, never a default: a roofline or utilization against a guessed
peak is not a measurement."""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float        # FLOP/s, dense bf16 on the MXUs
    int8_ops: float          # OP/s
    hbm_bytes_per_s: float   # HBM bandwidth
    hbm_bytes: float         # HBM capacity
    ici_bits_per_s: float    # chip-to-chip interconnect
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12,
        int8_ops=393e12,
        hbm_bytes_per_s=819e9,
        hbm_bytes=16e9,
        ici_bits_per_s=1600e9,
        source="Google Cloud documentation, 'TPU v5e' (per-chip peaks)",
    ),
}


def peaks_for(device_kind: str) -> Peaks:
    """The peaks of ``device_kind``; raises ``KeyError`` for a chip
    that is not in ``PEAKS``."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind={device_kind!r} (known: {sorted(PEAKS)})"
        ) from None
