"""Model FLOPs of the window's training and evaluation (bench/flops.py,
recomputation excluded) over window x chips x the chip's bf16 peak."""
from bench.peaks import peak


def read(ctx):
    if ctx["model_flops"] <= 0:
        return None
    return 100.0 * ctx["model_flops"] / (
        ctx["window_s"] * ctx["chips"] * peak(ctx["device_kind"])["bf16_flops"])
