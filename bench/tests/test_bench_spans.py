"""The reduction of the program's spans against the device trace
(``bench/spans.py``), on hand-made traces whose answers are known, on a
CPU profile of the tiny cell's rounds, and on a recorded chip trace."""
from __future__ import annotations

from pathlib import Path

import pytest
from bench_tinycell import BENCH, tiny_root  # noqa: F401

from bench import spans, trace

MS = 1_000_000
RECORDED = Path(__file__).parent / "data" / "cnn-default.xplane.pb.gz"
NAMES = ("round", "batch.labeled", "batch.clients", "prefetch.wait",
         "phase.supervised", "phase.cross_entity", "broadcast", "fedavg",
         "sync", "eval")


def driver_line() -> list:
    """Two rounds in a 100 ms window, as the driver thread saw them."""
    spans_ms = [
        ("bench.window", 0, 100),
        ("bench.round", 5, 60),
        ("semisfl.round", 5, 58),
        ("semisfl.batch.labeled", 6, 12),
        ("semisfl.phase.supervised", 12, 14),
        ("semisfl.broadcast", 14, 16),
        ("semisfl.batch.clients", 16, 22),
        ("semisfl.phase.cross_entity", 22, 24),
        ("semisfl.fedavg", 24, 28),
        ("semisfl.sync", 28, 50),
        ("bench.round", 60, 95),
        ("semisfl.round", 60, 90),
        ("semisfl.prefetch.wait", 62, 70),
        ("semisfl.sync", 75, 85),
    ]
    return [(n, s * MS, e * MS) for n, s, e in spans_ms]


def hand_trace(second_chip_busy: bool = False) -> spans.Trace:
    """Chip 0 runs the supervised phase 13-20 and 71-74, the cross-entity
    phase 23-25 and 74-78, and an eager FedAvg op 26-27 (busy 17 ms of
    100).  The prefetch worker's line builds batches 30-55 and 70-95,
    while the chip sits idle, on its own line.  Optionally a second chip
    is busy through the whole window."""
    ops = {"/device:TPU:0": [("%while.1", 13 * MS, 20 * MS),
                             ("%while.2", 23 * MS, 25 * MS),
                             ("%fusion.3", 26 * MS, 27 * MS),
                             ("%while.1", 71 * MS, 74 * MS),
                             ("%while.2", 74 * MS, 78 * MS)]}
    modules = {"/device:TPU:0": [("jit_supervised_phase(7)", 13 * MS, 20 * MS),
                                 ("jit_cross_entity_phase(8)", 23 * MS,
                                  25 * MS),
                                 ("jit_mean(9)", 26 * MS, 27 * MS),
                                 ("jit_supervised_phase(7)", 71 * MS, 74 * MS),
                                 ("jit_cross_entity_phase(8)", 74 * MS,
                                  78 * MS)]}
    if second_chip_busy:
        ops["/device:TPU:1"] = [("%fusion.9", 0, 100 * MS)]
    driver = driver_line()
    worker = [("semisfl.batch.labeled", 30 * MS, 55 * MS),
              ("semisfl.batch.clients", 70 * MS, 95 * MS)]
    return spans.Trace(ops=ops, spans=[s for s in driver
                                       if s[0].startswith("bench.")],
                       threads=[worker, driver], modules=modules)


def test_innermost_span_owns_each_moment():
    pieces = spans.innermost(driver_line(), 0, 100 * MS)
    got = [(n, s // MS, e // MS) for n, s, e in pieces]
    assert got[:10] == [
        ("bench.window", 0, 5), ("semisfl.round", 5, 6),
        ("semisfl.batch.labeled", 6, 12), ("semisfl.phase.supervised", 12, 14),
        ("semisfl.broadcast", 14, 16), ("semisfl.batch.clients", 16, 22),
        ("semisfl.phase.cross_entity", 22, 24), ("semisfl.fedavg", 24, 28),
        ("semisfl.sync", 28, 50), ("semisfl.round", 50, 58)]
    assert got[-2:] == [("bench.round", 90, 95), ("bench.window", 95, 100)]
    # the pieces tile the window
    assert sum(e - s for _, s, e in pieces) == 100 * MS


def test_self_time_is_the_span_less_its_children():
    self_s = spans.self_seconds(driver_line(), 0, 100 * MS)
    # round 1: 5-58 less 6-50; round 2: 60-90 less 62-70 and 75-85
    assert self_s["semisfl.round"] == pytest.approx((53 - 44 + 30 - 18) / 1e3)
    assert self_s["bench.round"] == pytest.approx((2 + 5) / 1e3)
    assert self_s["semisfl.sync"] == pytest.approx(0.032)
    assert self_s["bench.window"] == pytest.approx(0.010)
    # a window-clipped reading
    part = spans.self_seconds(driver_line(), 10 * MS, 20 * MS)
    assert part == pytest.approx({"semisfl.batch.labeled": 0.002,
                                  "semisfl.phase.supervised": 0.002,
                                  "semisfl.broadcast": 0.002,
                                  "semisfl.batch.clients": 0.004})


def test_idle_goes_to_the_driver_threads_innermost_span():
    idle = spans.idle_by_span(hand_trace())
    assert idle == pytest.approx({
        "bench.window": 0.010, "semisfl.round": 0.017,
        "semisfl.batch.labeled": 0.006, "semisfl.phase.supervised": 0.001,
        "semisfl.batch.clients": 0.002, "semisfl.phase.cross_entity": 0.001,
        "semisfl.fedavg": 0.002, "semisfl.sync": 0.029,
        "semisfl.prefetch.wait": 0.008, "bench.round": 0.007})


def test_worker_line_spans_take_no_idle_time():
    t = hand_trace()
    with_worker = spans.idle_by_span(t)
    t.threads = [line for line in t.threads
                 if any(n == "bench.window" for n, _, _ in line)]
    assert spans.idle_by_span(t) == with_worker
    # the worker's batch builds (30-55, 70-95) overlap 45 ms of idle chip,
    # yet the batch group holds only the driver's own 16 ms
    assert spans.idle_shares(hand_trace())["batch"] == pytest.approx(16.0)


@pytest.mark.parametrize("second_chip_busy", [False, True])
def test_the_four_idle_shares_sum_to_the_idle_share(second_chip_busy):
    t = hand_trace(second_chip_busy)
    shares = spans.idle_shares(t)
    s = trace.summarize(t)
    idle_share = 100.0 * (1.0 - s["busy_s"] / s["window_s"])
    half = 0.5 if second_chip_busy else 1.0
    assert shares == pytest.approx({"batch": 16.0 * half,
                                    "fedavg": 2.0 * half,
                                    "sync": 29.0 * half,
                                    "other": 36.0 * half})
    assert sum(shares.values()) == pytest.approx(idle_share)


def test_phase_time_per_round_from_the_module_line():
    t = hand_trace()
    assert spans.rounds(t) == 2
    assert spans.module_seconds(t, "supervised_phase") == pytest.approx(
        (2, 0.010))
    assert spans.phase_ms(t, "supervised_phase") == pytest.approx(5.0)
    assert spans.phase_ms(t, "cross_entity_phase") == pytest.approx(3.0)
    # a name that is a prefix of another program's is not that program
    assert spans.phase_ms(t, "supervised") is None


def test_the_readings_on_a_trace_with_and_without_program_spans():
    assert spans.metrics(hand_trace()) == pytest.approx({
        "idle.batch_share": 16.0, "idle.fedavg_share": 2.0,
        "idle.sync_share": 29.0, "idle.other_share": 36.0,
        "phase.supervised_ms": 5.0, "phase.cross_entity_ms": 3.0})
    # a program without the spans and names: nothing to read, no error
    bare = hand_trace()
    bare.threads = [[s for s in line if s[0].startswith("bench.")]
                    for line in bare.threads]
    bare.modules = {d: [("jit_phase(1)", s, e) for _, s, e in ev]
                    for d, ev in bare.modules.items()}
    assert spans.metrics(bare) == {}


# ------------------------------------------------------------ CPU profile

@pytest.mark.parametrize("prefetch", [False, True])
def test_a_profiled_round_carries_every_span_nested_on_one_line(
        tiny_root, tmp_path, prefetch):  # noqa: F811
    import json

    import jax

    from bench import harness, traffic
    cell, cfg, mix = harness.load_cell("tiny", tiny_root)
    data = traffic.make_traffic(mix, cfg, 11)
    sys_, _ = harness.make_system(cfg, mix)
    sys_.prefetch = prefetch
    feed = harness.make_feed(sys_, cell, cfg, mix, data, 11)
    state = feed["state"]
    run = lambda st: sys_.run_round(st, feed["lab"], feed["clients"],
                                    feed["ctrl"], rng_np=feed["sel"])[0]
    try:
        state = run(state)                          # compile outside
        sys_.evaluate(state, data.test.x, data.test.y)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            for _ in range(2):
                state = run(state)
            sys_.evaluate(state, data.test.x, data.test.y)
            jax.block_until_ready(state)
        finally:
            jax.profiler.stop_trace()
    finally:
        sys_.close()
    tr = spans.load(trace.find_xplane(tmp_path))
    driver = [line for line in tr.threads
              if any(n == "semisfl.round" for n, _, _ in line)]
    assert len(driver) == 1, json.dumps(tr.threads)[:2000]
    driver = driver[0]
    rounds = [(s, e) for n, s, e in driver if n == "semisfl.round"]
    evals = [(s, e) for n, s, e in driver if n == "semisfl.eval"]
    assert len(rounds) == 2 and len(evals) == 1
    on_driver = {n for n, _, _ in driver}
    others = {n for line in tr.threads if line is not driver
              for n, _, _ in line}
    want = {"semisfl." + n for n in NAMES}
    if prefetch:
        assert {"semisfl.batch.labeled", "semisfl.batch.clients"} <= others
        assert want <= on_driver | others
    else:
        assert want - {"semisfl.prefetch.wait"} <= on_driver
        assert not others
    for n, s, e in driver:
        if n not in ("semisfl.round", "semisfl.eval"):
            assert any(a <= s and e <= b for a, b in rounds + evals), n


# ------------------------------------------------------------ chip trace

@pytest.fixture(scope="module")
def recorded():
    return spans.load(RECORDED)


def test_the_recorded_chip_trace_carries_every_span_and_program(recorded):
    names = {n for line in recorded.threads for n, _, _ in line}
    assert {"semisfl." + n for n in NAMES} <= names
    for phase in ("supervised_phase", "cross_entity_phase"):
        calls, secs = spans.module_seconds(recorded, phase)
        assert calls >= 1 and secs > 0, phase
        assert spans.phase_ms(recorded, phase) > 0
    # the prefetch worker's builds sit on a line of their own
    driver = spans.driver_thread(recorded)
    assert any(n.startswith("semisfl.batch.") for line in recorded.threads
               if line is not driver for n, _, _ in line)


def test_the_recorded_chip_traces_idle_shares_sum_to_its_idle_share(
        recorded):
    shares = spans.idle_shares(recorded)
    s = trace.summarize(recorded)
    assert set(shares) == {"batch", "fedavg", "sync", "other"}
    assert all(v >= 0 for v in shares.values())
    assert sum(shares.values()) == pytest.approx(
        100.0 * (1.0 - s["busy_s"] / s["window_s"]), abs=1e-6)
