"""Prompt tokens served from the prefix cache, as a share of the prompt
tokens of the requests that finished in the window (the engine's own
per-request count)."""


def read(ctx):
    records, window = ctx["facts"].get("records"), ctx["window"]
    if not records:
        return None
    done = [r for r in records.values()
            if r["finished"] is not None and r["finished"] <= window[1]]
    prompt = sum(len(r["prompt"]) for r in done)
    if not prompt:
        return None
    return 100.0 * sum(r["prefix_cached_tokens"] for r in done) / prompt
