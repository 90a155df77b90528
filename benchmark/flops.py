"""Operations and bytes of a call, computed from its shapes — the
yardstick's own arithmetic (the same as the program's
`utils/flops.attention_flops`, copied so that no later PR can move it).
"""

from __future__ import annotations


def attention_flops(m: int, n: int, dk: int, dv: int, *,
                    causal: bool = False, heads: int = 1) -> int:
    """Matrix-multiply operations of one attention: Q K^T (2 m n dk)
    and P V (2 m n dv).  The exponentials are not counted; ``causal``
    halves the score matrix."""
    total = 2 * m * n * (dk + dv) * heads
    return total // 2 if causal else total


def attention_bytes(m: int, n: int, dk: int, dv: int, *, itemsize: int,
                    heads: int = 1, kv_heads: int | None = None) -> int:
    """The least bytes one attention moves to and from memory: Q and
    the result once each, K and V once each."""
    kv_heads = heads if kv_heads is None else kv_heads
    return itemsize * (heads * m * (dk + dv) + kv_heads * n * (dk + dv))


def roofline_seconds(flops: float, nbytes: float, peak: dict,
                     flops_key: str = "bf16_flops_per_s") -> tuple[float, str]:
    """The least time one chip could take, and which roof sets it."""
    compute = flops / peak[flops_key]
    memory = nbytes / peak["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
