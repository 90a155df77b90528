"""What the six `startup.*` readers share: the program's own compile
log (`attention_tpu.obs.compiles`) from the start of the process to the
opening of the measured window.  Every one moves `setup_s` and is read
in every cell: the three counts are exact in one run, the seconds are
that one traced run's.

For the traced runs of a check the driver lays a PR's benchmark files
over the PARENT's checkout too, and a traced run that fails refuses the
PR: on a program that has no `obs/compiles.py` at all (the parent of
the PR that brought it) the readers return nothing and the line leaves
the six out.  Only that: a module that is there and does not import, or
lacks `summary`, fails the run."""

import importlib.util


def log_until(until: float):
    """`obs.compiles.summary` of everything stamped up to ``until`` on
    the log's clock, `time.perf_counter`; None for a program without
    the log."""
    if importlib.util.find_spec("attention_tpu.obs.compiles") is None:
        return None
    from attention_tpu.obs import compiles

    return compiles.summary(until=until)


def before_window(ctx):
    """The log before the window opened (the window's clock is the
    log's)."""
    return log_until(ctx["window"][0])


def _reading(ctx, key):
    log = before_window(ctx)
    return None if log is None else float(log[key])


def trace_s(ctx):
    """Python tracing of every jitted function to a jaxpr (the union of
    the trace events' intervals: a function traced inside another's
    trace counts once): the part the persistent cache never saves."""
    return _reading(ctx, "trace_s")


def lower_s(ctx):
    """jaxpr to MLIR module, likewise a union."""
    return _reading(ctx, "lower_s")


def compile_s(ctx):
    """The backend's compile, or the persistent cache's retrieval in
    its place."""
    return _reading(ctx, "compile_s")


def cache_misses(ctx):
    """Programs the persistent cache did not hold and was given: 0 in
    a warm run; any other reading explains a jump of `setup_s` by
    itself."""
    return _reading(ctx, "cache_misses")


def programs(ctx):
    """Programs compiled or loaded from the cache: the cell's step
    shapes, the weights' init and the helpers; the same for a tree in
    every run, so parent != change is the change's doing."""
    return _reading(ctx, "programs")


def rest_s(ctx):
    """`setup_s` less the time in which anything was traced, lowered or
    compiled (`all_s`, the union over the three kinds): imports, the
    chip, weights, the warm-up steps' execution, traffic, prefix fill.
    A `setup_s` that moves with this alone moved with the machine or
    the imports."""
    log = before_window(ctx)
    setup_s = ctx["values"].get("setup_s")
    if log is None or setup_s is None:
        return None
    return setup_s - log["all_s"]
