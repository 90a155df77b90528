"""Operations and bytes of latent attention (MLA) over the keys a
selector CHOSE, computed from its shapes and the step's own counts: the
work, not an implementation of it.

THE WORK is the published form's on the ATTENDED pairs alone: an
attended (query token, key) pair is one product of ``nope + rope`` and
one of ``v`` lanes a head, 2 heads (nope + rope + v) operations, as
`benchmark/mla_flops.py` counts a pair.  Bytes: of a slot's cache no
less than the rows ONE of its query rows attends can be read, ``min
(top_k, kv_len)`` rows once a sublayer at ``row`` values (the latent
and the shared rotary key) and the item size the configuration states;
each query token's rows come in (heads (nope + rope)) and go out
(heads v) once.  A floor whatever implements it: a kernel that walks
every live page of a slot and masks reads 24 times that at 49k keys.
"""

from __future__ import annotations


def sparse_attn_flops(attended_pairs: int, heads: int, nope: int, rope: int,
                      v: int) -> int:
    return 2 * heads * (nope + rope + v) * attended_pairs


def sparse_attn_bytes(kept_rows: int, tokens: int, *, row: int, heads: int,
                      nope: int, rope: int, v: int, itemsize: int) -> int:
    cache = kept_rows * row * itemsize
    rows = tokens * heads * (nope + rope + v) * itemsize
    return cache + rows
