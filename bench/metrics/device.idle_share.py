"""Share of the window in which no operation ran on the chip, from the
profiler trace (averaged over the chips the cell uses)."""


def read(ctx):
    s = ctx.get("trace_summary")
    if s is None or s["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
