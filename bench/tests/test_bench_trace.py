"""The reduction from a profiler trace to device numbers, on a small
hand-made trace whose answers are known."""
from __future__ import annotations

import pytest
from bench_tinycell import BENCH  # noqa: F401

from bench import trace

MS = 1_000_000


def hand_trace() -> trace.Trace:
    """A 100 ms window on two chips.  Chip 0: a fusion 10-30, an
    all-reduce 25-45 (exposed 30-45), a fusion 60-70.  Chip 1: a fusion
    0-50 and an all-gather 40-55 (exposed 50-55).  Host: a round 6-80,
    an eval 60-95."""
    return trace.Trace(
        ops={"/device:TPU:0": [("fusion.1", 10 * MS, 30 * MS),
                               ("all-reduce.2", 25 * MS, 45 * MS),
                               ("fusion.1", 60 * MS, 70 * MS)],
             "/device:TPU:1": [("fusion.1", 0, 50 * MS),
                               ("all-gather.3", 40 * MS, 55 * MS),
                               ("fusion.9", 150 * MS, 160 * MS)]},
        spans=[("bench.window", 0, 100 * MS),
               ("bench.round", 6 * MS, 80 * MS),
               ("bench.eval", 60 * MS, 95 * MS)])


def test_busy_union_idle_and_op_times():
    s = trace.summarize(hand_trace())
    assert s["window_s"] == pytest.approx(0.1)
    # chip 0 busy 10-45 and 60-70 = 45 ms, chip 1 busy 0-55 = 55 ms
    assert s["busy_s"] == pytest.approx(0.050)
    ops = dict(s["device_ops"])
    # fusion.1: (20 + 10 + 50) ms over two chips; fusion.9 is outside
    assert ops["fusion.1"] == pytest.approx(0.040)
    assert "fusion.9" not in ops


def test_collective_time_with_no_compute_beside_it():
    s = trace.summarize(hand_trace())
    assert s["collective_exposed_s"] == pytest.approx((0.015 + 0.005) / 2)


def test_idle_gaps_are_labelled_by_the_open_host_span():
    gaps = trace.summarize(hand_trace())["idle_gaps"]
    # chip 1: 55-100 (eval open at 77.5); chip 0: 70-100 (eval at 85),
    # 45-60 (round at 52.5), 0-10 (none at 5)
    assert gaps[0] == ["bench.eval", pytest.approx(0.045)]
    assert gaps[1] == ["bench.eval", pytest.approx(0.030)]
    assert gaps[2] == ["bench.round", pytest.approx(0.015)]
    assert gaps[3] == ["none", pytest.approx(0.010)]


def test_op_seconds_counts_calls_per_chip():
    calls, secs = trace.op_seconds(hand_trace(), r"^fusion\.1$")
    assert calls == pytest.approx(1.5) and secs == pytest.approx(0.040)


def test_op_labels_keep_the_name_the_op_name_and_the_mosaic_mark():
    text = ('%jvp__.1 = (f32[80,128]{1,0}) custom-call(%copy.5), '
            'custom_call_target="tpu_custom_call", metadata={op_name='
            '"jit(phase)/while/body/jvp()/pallas_call" stack_frame_id=26}, '
            'backend_config={"custom_call_config":{"body":"TUzvUgFN"}}')
    assert trace.op_label(text) == (
        "%jvp__.1 jit(phase)/while/body/jvp()/pallas_call tpu_custom_call")
    assert trace.op_label("%while.8 = (s32[]) while(%t)") == "%while.8"


def test_a_window_is_required():
    t = hand_trace()
    t.spans = [s for s in t.spans if s[0] != "bench.window"]
    with pytest.raises(ValueError, match="bench.window"):
        trace.summarize(t)


def test_clustering_loss_roofline_reads_the_mosaic_calls():
    from bench import flops, harness
    from bench.peaks import peak
    reader = harness.metric_readers()["clustering_loss_roofline"]
    mix = {"n_active": 5, "n_clients": 10, "client_batch": 16}
    ctx = {"mix": mix, "cfg": {"queue_len": 2048, "proj_dim": 64},
           "chips": 1, "device_kind": "TPU v5 lite", "trace": hand_trace()}
    assert reader.read(ctx) is None                   # no Mosaic call
    assert reader.read(dict(ctx, trace=None)) is None
    t = hand_trace()
    for events in t.ops.values():
        events += [
            ("%c.1 jit(phase)/pallas_call tpu_custom_call", 80 * MS, 82 * MS),
            ("%c.2 jit(phase)/pallas_call tpu_custom_call", 84 * MS, 88 * MS)]
    chip = peak("TPU v5 lite")
    least = sum(max(f / chip["bf16_flops"], n / chip["hbm_bytes_per_s"])
                for f, n in (flops.eq5_call_cost(80, 2048, 64, d)
                             for d in ("fwd", "bwd")))
    # one forward and one backward call on each chip, 6 ms a chip
    assert reader.read(dict(ctx, trace=t)) == pytest.approx(
        100 * least / 0.006)

