"""FLOPs accounting and MXU-utilization math.

The reference publishes only relative speedups (BASELINE.md); this repo's
north-star metric is absolute — attention GFLOPs/chip and % of peak
matmul FLOPs (BASELINE.json).  These helpers define that accounting in
one place so bench and tests agree.
"""

from __future__ import annotations

import jax

# Published peak dense bf16 matmul TFLOP/s per chip, by `device_kind`
# prefix.  A device that is not in the table is an error, not a default.
# v5e (JAX reports it as "TPU v5 lite"): 197 TFLOP/s bf16 and 819 GB/s
# of HBM bandwidth — Google Cloud documentation, "TPU v5e"; 394 is its
# int8 TOP/s, not the bf16 peak.  The other rows are the same
# documentation's per-generation system-architecture pages.
_PEAK_TFLOPS_BF16 = {
    "TPU v4": 275.0,
    "TPU v5 lite": 197.0,
    "TPU v5e": 197.0,
    "TPU v5": 459.0,  # v5p
    "TPU v6 lite": 918.0,
}


class UnknownDeviceError(LookupError):
    """The device has no entry in the published-peaks table, so no
    utilization or roofline share can be stated for it."""


def attention_flops(m: int, n: int, dk: int, dv: int, *, causal: bool = False,
                    heads: int = 1) -> int:
    """Matmul FLOPs for one attention: QK^T (2·m·n·dk) + P·V (2·m·n·dv).

    Softmax exp/add FLOPs are excluded — the metric is *matmul-FLOPs*
    utilization (BASELINE.json).  ``causal`` halves the score matrix.
    """
    total = 2 * m * n * (dk + dv) * heads
    return total // 2 if causal else total


def peak_flops(device=None) -> float:
    """Peak bf16 matmul FLOP/s for the given (default: first) device;
    `UnknownDeviceError` off the table (e.g. the CPU backend)."""
    if device is None:
        device = jax.devices()[0]
    kind = getattr(device, "device_kind", "")
    for prefix, tflops in _PEAK_TFLOPS_BF16.items():
        if kind.startswith(prefix):
            return tflops * 1e12
    raise UnknownDeviceError(
        f"no published peak for device kind {kind!r} (platform "
        f"{getattr(device, 'platform', '?')}); utilization is only "
        f"defined on {sorted(_PEAK_TFLOPS_BF16)}"
    )


def utilization(flops: int, seconds: float, device=None) -> float:
    """Fraction of peak matmul FLOPs achieved."""
    return flops / seconds / peak_flops(device)
