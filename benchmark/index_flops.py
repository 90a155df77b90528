"""Operations and bytes of a lightning indexer's scoring over a paged
cache of index keys, computed from its shapes and the step's own
counts: the work, not an implementation of it.

A scored (query token, key) pair is one product of ``dim`` lanes a
selector head and the weighted sum over the heads: 2 heads dim
operations.  Bytes: a live (slot, page) pair of the index pool is read
ONCE a sublayer at ``dim`` values a token and the item size the
configuration states (every row of a slot shares the read); each query
token's selector queries come in once (heads dim values) with its
head weights (heads float32).
"""

from __future__ import annotations


def index_flops(qk_pairs: int, heads: int, dim: int) -> int:
    return 2 * heads * dim * qk_pairs


def index_bytes(kv_pages: int, tokens: int, *, page: int, heads: int,
                dim: int, itemsize: int) -> int:
    cache = kv_pages * page * dim * itemsize
    rows = tokens * heads * (dim * itemsize + 4)
    return cache + rows
