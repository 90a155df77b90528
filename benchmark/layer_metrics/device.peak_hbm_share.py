"""Peak bytes in use on the fullest chip after the window
(`memory_stats()["peak_bytes_in_use"]`), as a share of the chip's
published memory."""


def read(ctx):
    peak = ctx["device"].get("memory_peak_bytes")
    if not peak:
        return None
    return 100.0 * peak / ctx["peaks"]["hbm_bytes"]
