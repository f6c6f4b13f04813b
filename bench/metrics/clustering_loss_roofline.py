"""Share of the Eq. (5) kernel's roofline: the least time its calls in the
window need -- for each call the larger of its FLOPs over the chip's bf16
peak and its bytes over the HBM bandwidth, ``flops.eq5_call_cost`` at the
cell's B, Q and d -- over the device time the trace gives those calls.

The calls are the Mosaic custom calls of the trace: in these cells the
Eq. (5) forward and backward are the only ones, one of each per
cross-entity step.  B is the anchors one chip holds in a step."""
from bench import flops, trace
from bench.peaks import peak

MOSAIC = r"tpu_custom_call"


def read(ctx):
    t = ctx.get("trace")
    if t is None:
        return None
    calls, secs = trace.op_seconds(t, MOSAIC)
    if calls <= 0 or secs <= 0:
        return None
    mix, cfg, chip = ctx["mix"], ctx["cfg"], peak(ctx["device_kind"])
    b = min(mix["n_active"], mix["n_clients"]) * mix["client_batch"] \
        // ctx["chips"]
    least = 0.0
    for direction in ("fwd", "bwd"):
        f, nbytes = flops.eq5_call_cost(b, cfg["queue_len"], cfg["proj_dim"],
                                        direction)
        least += max(f / chip["bf16_flops"], nbytes / chip["hbm_bytes_per_s"])
    return 100.0 * least * (calls / 2) / secs
