"""Host-clock time inside ``SemiSFLSystem.evaluate`` over the window,
from the benchmark's own timing around each call."""


def read(ctx):
    if not ctx["eval_s"]:
        return None
    return 100.0 * sum(ctx["eval_s"]) / ctx["window_s"]
