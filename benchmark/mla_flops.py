"""Operations and bytes of latent attention (MLA) over a paged latent
cache, computed from its shapes and the step's own counts: the work,
not an implementation of it.

THE WORK is the published form's: a (query token, key) pair is one
product of ``nope + rope`` and one of ``v`` lanes a head, 2 heads (nope
+ rope + v) operations.  A program that attends in the absorbed form
(queries taken into the latent space, 576 and 512 lanes a head where
the published form has 192 and 128) does 3.4 times that and cannot
pass 29% of the compute roof; the count does not follow it.  Bytes: a
live (slot, page) pair is read ONCE a sublayer at ``row`` values a
token (the latent and the shared rotary key, whatever the head count)
and the item size the configuration states; each query token's rows
come in (heads (nope + rope)) and go out (heads v) once.
"""

from __future__ import annotations


def mla_flops(qk_pairs: int, heads: int, nope: int, rope: int, v: int) -> int:
    return 2 * heads * (nope + rope + v) * qk_pairs


def mla_bytes(kv_pages: int, tokens: int, *, page: int, row: int, heads: int,
              nope: int, rope: int, v: int, itemsize: int) -> int:
    cache = kv_pages * page * row * itemsize
    rows = tokens * heads * (nope + rope + v) * itemsize
    return cache + rows
