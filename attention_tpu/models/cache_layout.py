"""What each layer of a model keeps between the engine's steps: the
MODEL knows its layers' kinds, so the model says it, once
(`TinyDecoder.cache_layout`), a `LayerCache` a layer; `ServingEngine`
allocates, hands to the jitted step and takes back what the
`CacheLayout` lists and names no kind doing so.  A new kind of layer
adds an entry where the layer is defined."""

from __future__ import annotations

import operator
from typing import Any, Callable, NamedTuple

from attention_tpu.models.moe import PackedTokens
from attention_tpu.ops.gated_delta import RaggedStateStep
from attention_tpu.ops.ragged_paged import RaggedPagedStep

#: the ids a layer's arrays are indexed by: a request's pages, the
#: pages of its second page space, its state row
PAGES, WINDOW_PAGES, STATE_ROWS = "pages", "window_pages", "state_rows"
PAGE = -1       # in an array's shape: the engine's page size


class LayerCache(NamedTuple):
    """One layer's arrays between steps: the ids that index their
    leading axis; each array's row shape and dtype (None: the engine's
    cache dtype); ``step(arrays, index)``, the cache the layer's
    ``__call__`` takes, made of them and a step's `engine.RaggedStepIndex`;
    ``kept(cache)``, the arrays back out of the cache the layer returned."""

    space: str
    arrays: tuple[tuple[tuple[int, ...], Any], ...]
    step: Callable[[tuple, Any], Any]
    kept: Callable[[Any], tuple] = operator.itemgetter(slice(2))


# ``step`` (and ``kept``) of the caches `models/transformer.py`'s layers
# take: K and V pools; ONE latent pool and no V a sublayer, a step each;
# a latent pool and its selector's index pool; a state and a conv tail
def kv_step(arrays, index):
    return RaggedPagedStep(*arrays, *index[:7])


def latent_steps(arrays, index):
    return tuple(RaggedPagedStep(pool, None, *index[:7]) for pool in arrays)


def latents_kept(steps):
    return tuple(step.k_pool for step in steps)


def indexed_step(arrays, index):
    return RaggedPagedStep(arrays[0], None, *index[:7], index_pool=arrays[1])


indexed_kept = operator.attrgetter("k_pool", "index_pool")


def state_step(arrays, index):
    return RaggedStateStep(*arrays, index.state_rows, index.kv_lens,
                           index.cu_q_lens, index.token_slot, index.q_span)


class CacheLayout(NamedTuple):
    """A model's `LayerCache` a layer, None for a layer that keeps nothing
    (it is told which tokens are pads), and the engine's whole-model
    questions.  Hashable and immutable."""

    layers: tuple[LayerCache | None, ...]
    #: may the mesh engine shard the pools' KV heads
    shard_kv_heads: bool = True
    #: what a feature that carries the K and V pages of ONE page space
    #: alone raises for this model, and its message after the feature
    pages_only_refusal: tuple[type, str] | None = None

    @property
    def state_rows(self) -> bool:
        """Whether a request holds a state row and a step's buffer them."""
        return any(c and c.space == STATE_ROWS for c in self.layers)

    @property
    def window_table(self) -> bool:
        """Whether a step's buffer carries a second page space's table."""
        return any(c and c.space == WINDOW_PAGES for c in self.layers)

    def steps(self, pools, index) -> tuple:
        """Each layer's cache for a packed step, of its ``pools`` and
        the step's shared ``index``; a layer of the second page space
        reads the window's table in the page table's place."""
        window = index._replace(page_table=index.window_table)
        return tuple(
            PackedTokens(index.token_slot) if c is None
            else c.step(arrays, window if c.space == WINDOW_PAGES else index)
            for c, arrays in zip(self.layers, pools, strict=True))

    def pools(self, steps) -> tuple:
        """The arrays out of the caches the layers handed back."""
        return tuple(c and c.kept(step)
                     for c, step in zip(self.layers, steps, strict=True))
