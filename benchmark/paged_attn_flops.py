"""Operations and bytes of paged grouped-query attention over a step's
packed tokens, computed from its shapes and the step's own counts: the
work, not an implementation of it.

THE WORK: an attended (query token, key) pair is one product of
``head_dim`` lanes for the score and one for the value in every QUERY
head, 4 heads head_dim operations.  Bytes: of a slot's cache no less
than the pages that hold a key SOME query row of the step attends can
be read, K and V of every key-value head once a layer at the item size
the configuration states (``band_pages`` counts those pages, a layer of
each kind apart: a window layer's are the pages its rows' windows reach,
a full layer's all the slot has); each query token's rows come in and
its result goes out once a layer (``heads head_dim`` each).  A floor
whatever implements it: a kernel that walks pages below the band, or a
page twice, reads more and its share of this roofline is lower; none
can read less, so no share passes 100%.
"""

from __future__ import annotations


def paged_attn_flops(attended_pairs: int, heads: int, head_dim: int) -> int:
    return 4 * heads * head_dim * attended_pairs


def page_bytes(kv_heads: int, page: int, head_dim: int, *,
               itemsize: int) -> int:
    """K and V of one page in one layer."""
    return 2 * kv_heads * page * head_dim * itemsize


def paged_attn_bytes(band_pages: int, tokens: int, *, heads: int,
                     kv_heads: int, page: int, head_dim: int,
                     itemsize: int) -> int:
    """``band_pages`` and ``tokens`` summed over the layers (a layer's
    pages by its kind, its tokens the step's)."""
    cache = band_pages * page_bytes(kv_heads, page, head_dim,
                                    itemsize=itemsize)
    rows = tokens * 2 * heads * head_dim * itemsize
    return cache + rows
