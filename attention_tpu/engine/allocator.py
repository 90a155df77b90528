"""Block allocator with a hash-keyed prefix cache over `PagePool`.

The engine's memory layer.  One logical `PagePool` serves every model
layer: the engine keeps per-layer physical pools (same page geometry),
so a single page-id allocation is valid in all of them and one table
row per request drives the whole stack — exactly the id discipline
`generate_paged` already uses (its per-layer pools replay identical
allocation sequences).

Prefix cache (vLLM-style, page granularity): committed prompt pages
are published under a content key — ``tuple(tokens[:i * page_size])``
for the i-th page, i.e. the exact token prefix the page's KV encodes —
and a later request whose prompt starts with the same tokens adopts
the pages by reference (`PagePool.incref`) instead of recomputing
them.  Exact-tuple keys rather than a digest: collisions would silently
serve another prompt's KV, and at serving-trace scale the dict is
small.  The cache holds its own reference on every published page, so
pages survive their computing request; eviction is LRU over *leaf*
entries nobody else references (refcount 1 = cache-only), which keeps
chains consistent — a parent page is only evictable after every longer
prefix built on it is gone.

Watermark: admission-path allocations must leave ``watermark_pages``
free (a reserve so already-running requests can keep appending decode
tokens); decode-path allocations may drain the reserve, then the
cache, and only then fail — the scheduler turns that failure into
preemption-by-recompute.

State slots: a model with recurrent layers keeps, beside its pages,
one fixed-size state per request (``state_slots`` rows of the engine's
state pools).  A request takes a slot at admission and gives it back
with its pages (`release`).  Pages do not hold that state, so for such
a model the prefix cache matches and publishes nothing: a hit would
skip tokens whose state nobody kept.

TWO PAGE SPACES (``window_pool``): a model whose sliding-window layers
stand beside full-attention layers keeps the window layers' K and V in
pools of their own, under page ids of their own.  A request's
``window_pages`` is parallel to its ``pages`` (entry ``j`` is logical
page ``j`` of either space) and holds -1 where the request holds no
window page: below its BAND.  A step's attention kernel reads, of a
window layer, the pages that reach back ``window + q_tile - 1`` keys
from the slot's newest (`ops.ragged_paged._band_window`), ``band``
here with the widest tile the engine dispatches; a page that lies
wholly below ``computed_tokens + 1 - band`` can never be read again,
and `release_window` gives it back (`window_pages_bound` is what a
request can hold).  The prefix tree's entry holds a window page id or
none: `commit_prefix` keeps window pages for the committed prefix's
trailing `tail_blocks` blocks, and `lookup_prefix` returns the longest
match whose trailing ``tail_blocks`` all have one, since the first
step after the hit reads them.  Both pools are evicted in ONE LRU
order (``last_use`` of the shared entries): a full-pool eviction drops
a leaf entry and the window page it holds; a window-pool eviction takes
the window page of the least recently used entry that has one nobody
else references, leaf or not, and leaves the entry (a hit through it
gets shorter).  A model with one kind of attention layer has no window
pool, and none of this runs.
"""

from __future__ import annotations

import dataclasses

from attention_tpu import obs
from attention_tpu.ops.paged import OutOfPagesError, PagePool

_ALLOC_PAGES = obs.counter("engine.allocator.pages_allocated",
                           "pages handed out, by path")
_OOM = obs.counter("engine.allocator.oom",
                   "OutOfPagesError raises, by path")
_WATERMARK = obs.counter("engine.allocator.watermark_trips",
                         "admission allocations refused by the reserve")
_PREFIX_HITS = obs.counter("engine.allocator.prefix_hits")
_PREFIX_MISSES = obs.counter("engine.allocator.prefix_misses")
_PREFIX_HIT_TOKENS = obs.counter("engine.allocator.prefix_hit_tokens")
_PREFIX_EVICTIONS = obs.counter("engine.allocator.prefix_evictions")
_STATE_SLOTS = obs.gauge("engine.state.slots_in_use",
                         "recurrent-state slots held by running requests")
_WINDOW_RELEASED = obs.counter(
    "engine.allocator.window_released",
    "window pages given back as a request's band slid past them")


def pages_for_tokens(n_tokens: int, page_size: int) -> int:
    """Pages needed to hold ``n_tokens`` KV rows (>= 1 row per page)."""
    return -(-n_tokens // page_size)


@dataclasses.dataclass
class _PrefixEntry:
    key: tuple[int, ...]          # the token prefix this page completes
    page: int                     # physical page holding its last page's KV
    parent: tuple[int, ...] | None
    children: set = dataclasses.field(default_factory=set)
    last_use: int = 0
    # the window pool's page of the same tokens (two page spaces); None
    # where the cache keeps none: outside a committed prefix's tail, or
    # evicted
    window_page: int | None = None


class BlockAllocator:
    """Watermark-guarded page allocation + prefix cache for one pool."""

    def __init__(self, pool: PagePool, page_size: int, *,
                 watermark_pages: int = 0, state_slots: int = 0,
                 window_pool: PagePool | None = None, band: int = 0):
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if not (0 <= watermark_pages < pool.num_pages):
            raise ValueError(
                f"watermark_pages {watermark_pages} outside "
                f"[0, {pool.num_pages})"
            )
        self.pool = pool
        self.page_size = page_size
        self.watermark_pages = watermark_pages
        # > 0: the model keeps a recurrent state per request
        self.state_slots = state_slots
        self._free_state_slots = list(range(state_slots))
        # the second page space, and the keys a window layer's kernel
        # reaches back from a slot's newest at the widest query tile
        if window_pool is not None and not (
                band >= 1 and watermark_pages < window_pool.num_pages):
            raise ValueError(
                f"a window pool needs a band >= 1 (got {band}) and more "
                f"than the watermark's {watermark_pages} pages (has "
                f"{window_pool.num_pages})")
        self.window_pool = window_pool
        self.band = band
        self.window_pages_released = 0
        self._prefix: dict[tuple[int, ...], _PrefixEntry] = {}
        # counters the metrics layer reports
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.prefix_hit_tokens = 0
        self.prefix_evictions = 0

    # -- capacity ---------------------------------------------------------

    @property
    def cached_pages(self) -> int:
        return len(self._prefix)

    def _evictable(self) -> list[_PrefixEntry]:
        """Leaf entries whose page only the cache references."""
        return [
            e for e in self._prefix.values()
            if not e.children and self.pool.refcount(e.page) == 1
        ]

    def evict_lru(self) -> int | None:
        """Evict the least-recently-used evictable prefix page; returns
        the freed page id, or None when nothing is evictable."""
        victims = self._evictable()
        if not victims:
            return None
        victim = min(victims, key=lambda e: (e.last_use, e.key))
        del self._prefix[victim.key]
        if victim.parent is not None and victim.parent in self._prefix:
            self._prefix[victim.parent].children.discard(victim.key)
        self.pool.free([victim.page])
        if victim.window_page is not None:
            self.window_pool.free([victim.window_page])
        self.prefix_evictions += 1
        _PREFIX_EVICTIONS.inc()
        return victim.page

    def evict_window_lru(self) -> int | None:
        """Take the window page of the least recently used entry whose
        window page only the cache references (the same clock and
        tie-break as `evict_lru`); the entry stays.  Returns the freed
        window page id, or None when there is none to take."""
        victims = [e for e in self._prefix.values()
                   if e.window_page is not None
                   and self.window_pool.refcount(e.window_page) == 1]
        if not victims:
            return None
        victim = min(victims, key=lambda e: (e.last_use, e.key))
        page, victim.window_page = victim.window_page, None
        self.window_pool.free([page])
        self.prefix_evictions += 1
        _PREFIX_EVICTIONS.inc()
        return page

    def allocate(self, n: int, *, for_decode: bool = False,
                 window: bool = False) -> list[int]:
        """Allocate ``n`` pages, evicting LRU prefix pages as needed;
        with ``window``, of the window pool.

        Admission/prefill calls (``for_decode=False``) must leave the
        watermark reserve free *after* the allocation; decode appends
        may drain it.  Raises `OutOfPagesError` when even full eviction
        cannot satisfy the request — the scheduler's preemption signal.
        """
        if n == 0:
            return []
        path = "decode" if for_decode else "admit"
        pool, evict = ((self.window_pool, self.evict_window_lru) if window
                       else (self.pool, self.evict_lru))
        with obs.span("allocator.alloc"):
            reserve = 0 if for_decode else self.watermark_pages
            # evict until the allocation fits above the reserve;
            # evicting a leaf can expose its parent, so the loop
            # re-scans each round
            while pool.free_pages < n + reserve:
                if evict() is None:
                    _OOM.inc(path=path)
                    if not for_decode:
                        _WATERMARK.inc()
                    raise OutOfPagesError(
                        f"allocation of {n} page(s) would breach the "
                        f"{'decode floor' if for_decode else 'watermark'}"
                        f": free {pool.free_pages}"
                        f"{' window pages' if window else ''}, nothing "
                        f"evictable, reserve {reserve}"
                    )
            _ALLOC_PAGES.inc(n, path=path)
            return pool.alloc(n)

    def free(self, pages) -> None:
        """Drop the caller's reference on ``pages`` (cache references,
        if any, keep prefix pages alive for future hits)."""
        self.pool.free(pages)

    # -- recurrent-state slots --------------------------------------------

    @property
    def state_slots_in_use(self) -> int:
        return self.state_slots - len(self._free_state_slots)

    @property
    def has_free_state_slot(self) -> bool:
        """True for a pages-only model, which needs none."""
        return not self.state_slots or bool(self._free_state_slots)

    def take_state_slot(self) -> int:
        """The lowest free state slot (-1 for a pages-only model).
        The slot's rows hold whatever the last owner left: a request
        that starts at token 0 starts from a zero state in the kernel."""
        if not self.state_slots:
            return -1
        if not self._free_state_slots:
            raise OutOfPagesError(
                f"all {self.state_slots} recurrent-state slots are held")
        slot = self._free_state_slots.pop(0)
        _STATE_SLOTS.set(float(self.state_slots_in_use))
        return slot

    def release(self, req) -> None:
        """Give back everything ``req`` holds: its reference on its
        pages and its state slot."""
        if req.pages:
            self.free(req.pages)
        req.pages = []
        held = [p for p in req.window_pages if p >= 0]
        if held:
            self.window_pool.free(held)
        req.window_pages = []
        if req.state_slot >= 0:
            self._free_state_slots.append(req.state_slot)
            self._free_state_slots.sort()
            req.state_slot = -1
            _STATE_SLOTS.set(float(self.state_slots_in_use))

    # -- the window layers' page space ------------------------------------

    @property
    def tail_blocks(self) -> int:
        """Trailing blocks of a page-aligned prefix whose window pages
        the first step after a hit on it can read."""
        return pages_for_tokens(self.band, self.page_size)

    def window_pages_bound(self, step_tokens: int = 1) -> int:
        """The most window pages a request holds while it issues
        ``step_tokens`` tokens a step: its band, the tokens, and one
        page more for where the band's start falls in a page."""
        return pages_for_tokens(self.band + step_tokens - 1,
                                self.page_size) + 1

    def _first_band_page(self, computed_tokens: int) -> int:
        """The lowest logical page a request with ``computed_tokens``
        keys cached can still read of a window layer: its next step
        leaves ``computed_tokens + 1`` keys or more, and reaches back
        ``band`` from there."""
        return max(computed_tokens + 1 - self.band, 0) // self.page_size

    def release_window(self, req) -> int:
        """Give back the window pages ``req``'s band has slid past (its
        reference on them: a page the cache also holds stays cached);
        returns how many."""
        # what a request holds is one run of pages: below it everything
        # was given back already, or never held (a prefix hit's -1s)
        gone = []
        for j in range(min(self._first_band_page(req.computed_tokens),
                           len(req.window_pages)) - 1, -1, -1):
            if req.window_pages[j] < 0:
                break
            gone.append(j)
        if gone:
            self.window_pool.free([req.window_pages[j] for j in gone])
            for j in gone:
                req.window_pages[j] = -1
            self.window_pages_released += len(gone)
            _WINDOW_RELEASED.inc(len(gone))
        return len(gone)

    def cover(self, req, cover_tokens: int, *, for_decode: bool) -> None:
        """Extend ``req``'s pages to hold ``cover_tokens`` tokens, in
        both page spaces where there are two.  What was allocated
        before an `OutOfPagesError` stays the request's."""
        need = pages_for_tokens(cover_tokens, self.page_size)
        if need > len(req.pages):
            req.pages.extend(self.allocate(need - len(req.pages),
                                           for_decode=for_decode))
        if self.window_pool is not None and need > len(req.window_pages):
            req.window_pages.extend(self.allocate(
                need - len(req.window_pages), for_decode=for_decode,
                window=True))

    # -- prefix cache -----------------------------------------------------

    def _prefix_limit(self, toks: tuple) -> int:
        """Pages of ``toks`` a prefix match may cover: all but the last
        token's, and none for a model with recurrent state."""
        if self.state_slots:
            return 0
        return (len(toks) - 1) // self.page_size

    def peek_prefix(self, tokens) -> int:
        """Pages of the longest cached page-aligned prefix of
        ``tokens`` WITHOUT taking references or touching hit stats /
        LRU clocks — the multi-replica router's side-effect-free probe
        (routing by cache contents must not perturb the cache, or the
        probe of a replica that loses the routing race would still
        refresh its entries)."""
        toks = tuple(tokens)
        limit = self._prefix_limit(toks)
        chain = []
        for i in range(1, limit + 1):
            entry = self._prefix.get(toks[: i * self.page_size])
            if entry is None:
                break
            chain.append(entry)
        return self._window_hit(chain)

    def cached_chain(self, tokens) -> list[int]:
        """Physical pages of the longest cached page-aligned prefix of
        ``tokens`` — `peek_prefix`'s page-id twin, equally
        side-effect-free (no increfs, no hit stats, no LRU touches).
        The fleet prefix-store import path reads it to splice
        store-imported pages onto the end of the locally cached chain
        before committing the extended prefix."""
        toks = tuple(tokens)
        limit = self._prefix_limit(toks)
        pages: list[int] = []
        for i in range(1, limit + 1):
            entry = self._prefix.get(toks[: i * self.page_size])
            if entry is None:
                break
            pages.append(entry.page)
        return pages

    def lookup_prefix(self, tokens, *, now: int,
                      window_out: list | None = None) -> list[int]:
        """Longest cached page-aligned prefix of ``tokens``; increfs and
        returns the matched pages (caller owns one reference each).
        With two page spaces ``window_out`` receives the hit's window
        pages, parallel to the result: the window page of each of the
        trailing `tail_blocks` (an own reference taken) and -1 below.

        At least one token is always left uncached — the last prompt
        token must run through the model to produce the logits the
        first sampled token comes from.
        """
        toks = tuple(tokens)
        limit = self._prefix_limit(toks)
        chain: list[_PrefixEntry] = []
        for i in range(1, limit + 1):
            entry = self._prefix.get(toks[: i * self.page_size])
            if entry is None:
                break
            chain.append(entry)
        del chain[self._window_hit(chain):]
        for entry in chain:
            entry.last_use = now
        pages = [entry.page for entry in chain]
        if pages and window_out is not None and self.window_pool:
            tail = [e.window_page for e in chain[-self.tail_blocks:]]
            self.window_pool.incref(tail)
            window_out[:] = [-1] * (len(chain) - len(tail)) + tail
        if pages:
            self.pool.incref(pages)
            self.prefix_hits += 1
            self.prefix_hit_tokens += len(pages) * self.page_size
            _PREFIX_HITS.inc()
            _PREFIX_HIT_TOKENS.inc(len(pages) * self.page_size)
        else:
            self.prefix_misses += 1
            _PREFIX_MISSES.inc()
        return pages

    def _window_hit(self, chain) -> int:
        """Entries of a matched ``chain`` a hit may cover: all of them
        with one page space, else the longest run from the start whose
        trailing `tail_blocks` entries all hold a window page."""
        if self.window_pool is None:
            return len(chain)
        run = 0          # entries ending here that hold a window page
        best = 0
        for m, entry in enumerate(chain, start=1):
            run = run + 1 if entry.window_page is not None else 0
            if run >= min(m, self.tail_blocks):
                best = m
        return best

    def commit_prefix(self, tokens, pages, *, now: int,
                      window_pages=None) -> int:
        """Publish every full page of ``tokens`` (whose KV now lives in
        ``pages``, logical order) into the cache; returns how many new
        entries were inserted.  Already-published prefixes are just
        touched — a concurrent identical prompt that missed keeps its
        private pages and the first publisher's copy stays canonical
        (content-identical, so reads through either id agree).
        ``window_pages``, with two page spaces, is the committer's list
        parallel to ``pages``, -1 where it holds none."""
        if self.state_slots:
            return 0  # pages without the state they led to are no prefix
        toks = tuple(tokens)
        if len(pages) < len(toks) // self.page_size:
            raise ValueError(
                f"commit_prefix: {len(pages)} pages cannot cover "
                f"{len(toks) // self.page_size} full page(s) of tokens"
            )
        inserted = 0
        parent: tuple[int, ...] | None = None
        full = len(toks) // self.page_size
        for i in range(1, full + 1):
            key = toks[: i * self.page_size]
            entry = self._prefix.get(key)
            if entry is None:
                page = pages[i - 1]
                self.pool.incref([page])  # the cache's own reference
                entry = _PrefixEntry(key=key, page=page, parent=parent,
                                     last_use=now)
                self._prefix[key] = entry
                if parent is not None and parent in self._prefix:
                    self._prefix[parent].children.add(key)
                inserted += 1
            else:
                entry.last_use = now
            # two page spaces: the trailing `tail_blocks` entries keep
            # the committer's window page where they have none
            if (window_pages is not None and i > full - self.tail_blocks
                    and entry.window_page is None
                    and window_pages[i - 1] >= 0):
                entry.window_page = window_pages[i - 1]
                self.window_pool.incref([entry.window_page])
            parent = key
        return inserted
