"""Pages the window layers' page space holds (the running requests'
bands and the prefix cache's tails), as a share of the pages the same
layers would hold under the full layers' page table, in %, summed over
the traced steps, in a closed-loop cell: what the second page space
saves of a window layer's cache.  Under one page space a window layer
holds what a full layer holds, the full space's pages in use after the
same steps.  The counts are ``facts["window"]``
(`runners/serve_window.py`), from the engine's per-step metrics.  A
program with one page space leaves nothing to read."""


def read(ctx):
    work = ctx["facts"].get("window")
    if not work or not work.get("used_pages"):
        return None
    return 100.0 * work["window_used_pages"] / work["used_pages"]
