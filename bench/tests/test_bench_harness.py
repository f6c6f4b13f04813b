"""The benchmark harness on the CPU: finding cells, configurations and
per-layer metrics by name, the yardstick's arithmetic, the result line,
and the refusal to run without a TPU."""
from __future__ import annotations

import hashlib
import json
import math
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest
from bench_tinycell import (BENCH, REPO, make_tiny_root,  # noqa: F401
                            register_tiny, run_tiny, tiny_root)

from bench import compare, flops, harness, peaks

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def bench_json() -> dict:
    return json.loads((REPO / "BENCHMARK.json").read_text())


# ---------------------------------------------------------------- finding

def test_every_cell_config_and_metric_is_found_by_name():
    b = bench_json()
    assert harness.cell_names() == sorted(w["name"] for w in b["workloads"])
    for w in b["workloads"]:
        cell, cfg, mix = harness.load_cell(w["name"])
        assert (cell["config"], cell["traffic"], cell["chips"]) == \
            (w["config"], w["traffic"], w["chips"])
        file = next(c["file"] for c in b["configs"]
                    if c["name"] == w["config"])
        assert json.loads((REPO / file).read_text()) == cfg
        assert harness.reference_module(cfg).Reference
    readers = harness.metric_readers()
    for m in b["per_layer"]:
        assert callable(readers[m["name"]].read)


def test_a_new_cell_is_listed_with_no_other_edit(tmp_path):
    for sub in ("configs", "families", "traffic", "workloads"):
        shutil.copytree(BENCH / sub, tmp_path / sub)
    cell = json.loads((BENCH / "workloads/cnn-default.json").read_text())
    (tmp_path / "workloads/cnn-extra.json").write_text(json.dumps(cell))
    assert "cnn-extra" in harness.cell_names(tmp_path)
    assert harness.load_cell("cnn-extra", tmp_path)[1]["arch"] == "paper-cnn"
    with pytest.raises(FileNotFoundError, match="no workload named"):
        harness.load_cell("no-such-cell", tmp_path)


def test_benchmark_json_keeps_to_its_schema():
    b = bench_json()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert len(set(names)) == len(names)
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    cells = {w["name"] for w in b["workloads"]}
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
    for w in b["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)


@pytest.mark.parametrize("fault,message", [
    (None, r"configuration 'tiny' names no model family: it needs a "
           r"'family' key, the name of a file .*/families/<family>\.py"),
    ("no-such", r"configuration 'tiny' names model family 'no-such', but "
                r"there is no .*/families/no-such\.py")])
def test_a_configuration_must_name_a_family_file(tmp_path, fault, message):
    root = make_tiny_root(tmp_path)
    path = root / "configs/tiny.json"
    cfg = json.loads(path.read_text())
    if fault is None:
        del cfg["family"]
    else:
        cfg["family"] = fault
    path.write_text(json.dumps(cfg))
    with pytest.raises(FileNotFoundError, match=message):
        harness.load_cell("tiny", root)


# ------------------------------------------------- what the families keep
# Read on the parent commit of the per-family split, before the CNN code
# moved into bench/families/cnn.py: the default traffic at seed
# 2147483659, the round's FLOPs at K_s 15 and 3, and 400 evaluations.

PARENT = {
    "paper-cnn": {
        "traffic": "c5616c926ed65b60adb0133d3aca25f107a4cc0413b7c9e0"
                   "ca898e4f1155ab6f",
        "round_flops": {15: 45789224960, 3: 23809138688},
        "eval_flops": 5840384000},
    "vgg16-image100": {
        "traffic": "a5fb5a9a8a50115e75cf711f63670b2c221cf07741f40201"
                   "e317142ead4a971b",
        "round_flops": {15: 38815099535360, 3: 20183850500096},
        "eval_flops": 4858934681600},
}


def _config_and_mix(name: str) -> tuple:
    return (json.loads((BENCH / f"configs/{name}.json").read_text()),
            json.loads((BENCH / "traffic/default.json").read_text()))


@pytest.mark.parametrize("name", sorted(PARENT))
def test_the_traffic_arrays_are_the_parents(name):
    from bench import traffic
    cfg, mix = _config_and_mix(name)
    data = traffic.make_traffic(mix, cfg, 2147483659)
    h = hashlib.sha256()
    for a in (data.train.x, data.train.y, data.test.x, data.test.y,
              data.labeled, *data.parts):
        a = np.ascontiguousarray(a)
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    assert h.hexdigest() == PARENT[name]["traffic"]


@pytest.mark.parametrize("name", sorted(PARENT))
def test_the_round_and_eval_flops_are_the_parents(name):
    cfg, mix = _config_and_mix(name)
    assert {k: flops.round_flops(cfg, mix, k) for k in (15, 3)} == \
        PARENT[name]["round_flops"]
    assert flops.eval_flops(cfg, 400) == PARENT[name]["eval_flops"]


@pytest.mark.parametrize("name", sorted(PARENT))
def test_the_built_program_runs_what_the_configuration_states(name):
    cfg, mix = _config_and_mix(name)
    assert harness.make_system(cfg, mix)[1] == []


# ------------------------------------------------------------- arithmetic

def test_paper_vgg16_forward_flops():
    cfg = json.loads((BENCH / "configs/paper-vgg16.json").read_text())
    # 13 convs (8-13 at 18x18 in the program's layout, which pools only
    # where the width changes), FC-4096 x 2, classifier and head
    assert flops.forward_flops(cfg) == pytest.approx(15.69e9, rel=0.02)


@pytest.mark.parametrize("name", ["paper-cnn", "paper-vgg16"])
def test_forward_flops_match_xla_cost_analysis(name):
    """The count from shapes against XLA's own count of the program's
    forward pass (which adds element-wise work on top)."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core.split import apply_projection_head, init_projection_head
    from repro.core.split import pool_features
    from repro.models import build_model
    cfg = json.loads((BENCH / f"configs/{name}.json").read_text())
    pcfg = get_config(name)
    model = build_model(pcfg)
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(model.init, key)
    head = jax.eval_shape(lambda k: init_projection_head(k, pcfg), key)

    def fwd(p, h, x):
        feats, _, extras = model.bottom_apply(p["bottom"], {"images": x},
                                              mode="eval")
        out, _ = model.top_apply(p["top"], feats, extras=extras, mode="eval")
        return out["logits"], apply_projection_head(
            h, pcfg, pool_features(pcfg, feats))

    x = jax.ShapeDtypeStruct((1, cfg["image_size"], cfg["image_size"], 3),
                             jnp.float32)
    xla = jax.jit(fwd).lower(params, head, x).cost_analysis()["flops"]
    assert 1.0 <= xla / flops.forward_flops(cfg) <= 1.05


def test_eq5_call_cost_at_the_round_shape():
    b, q, d = 80, 2048, 64
    f, nbytes = flops.eq5_call_cost(b, q, d, "fwd")
    assert f == 2 * b * q * d == 20_971_520
    assert nbytes == 4 * (b * d + 2 * b + q * d + 2 * q + 3 * b) == 562_752
    f, nbytes = flops.eq5_call_cost(b, q, d, "bwd")
    assert f == 41_943_040 and nbytes == 562_752 + 4 * b * d
    with pytest.raises(ValueError):
        flops.eq5_call_cost(b, q, d, "sideways")


def test_round_flops_and_samples():
    cfg = json.loads((BENCH / "configs/paper-vgg16.json").read_text())
    mix = json.loads((BENCH / "traffic/default.json").read_text())
    assert flops.round_samples(mix, cfg, 15) == 15 * 32 + 4 * 5 * 16
    per_img = flops.forward_flops(cfg)
    total = flops.round_flops(cfg, mix, 15)
    # 15x32 + 4x80 images, each about four forward passes
    assert total == pytest.approx(800 * 4 * per_img, rel=0.01)


def test_peaks_are_keyed_by_device_kind():
    assert peaks.peak("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak("cpu")


@pytest.mark.parametrize("rounds,want", [
    (100, [15]), (110, [15, 10]), (209, [15, 10]), (210, [15, 10, 6]),
    (10_000, [15, 10, 6, 4, 3])])
def test_reachable_k_s(rounds, want):
    cfg = {"observation_period": 10, "adaptation_window": 10, "k_s": 15,
           "alpha": 1.5}
    assert harness.reachable_k_s(cfg, 3, rounds) == want


# ------------------------------------------------------------ comparison

def test_worst_leaf_gap_is_against_the_larger_of_leaf_and_median():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-6}
    prog = {"a": 1.0, "b": 2.0, "c": 2e-6}
    gap, leaf = compare.worst_leaf(prog, ref)
    assert leaf == "c" and gap == pytest.approx(1e-6 / 1.0)
    assert compare.worst_leaf({"a": 1.0}, ref)[0] == math.inf
    assert compare.worst_leaf({**prog, "a": math.nan}, ref)[0] == math.inf


def test_a_leaf_with_a_negligible_gradient_is_left_out_of_the_change():
    side = lambda change_c: {
        "metrics": [(1.0, 2.0, 0.5)],
        "grad": {"a": 1.0, "b": 1.0, "c": 1e-9},
        "change": {"params": {"a": 1.0, "b": 1.0, "c": change_c},
                   "teacher": {"a": 1.0, "b": 1.0, "c": change_c}}}
    r = compare.readings(side(1.0), side(0.0))
    assert r["change"]["value"] == 0.0 and r["loss"]["value"] == 0.0


# ---------------------------------------------------------------- a run

@pytest.fixture(scope="module")
def tiny_run(tiny_root):  # noqa: F811
    return run_tiny(tiny_root, trace=False)


def test_result_line_schema(tiny_run):
    r = tiny_run
    keys = [k for k in r if not k.startswith("_")]
    assert keys[:5] == ["correct", "attempted", "failed", "metrics",
                        "device"] and keys[-1] == "checks"
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 3
    assert set(r["metrics"]) == {"samples_per_s", "setup_s"}
    for m in r["metrics"].values():
        assert m["value"] > 0 and isinstance(m["unit"], str)
    assert set(r["device"]) >= {"platform", "kind", "count",
                                "memory_peak_bytes"}
    for name, c in r["checks"].items():
        assert 0 <= c["value"] <= c["limit"], name


def test_window_compiles_nothing_though_k_s_adapts(tiny_run):
    info = tiny_run["_info"]
    assert info["compiles_in_window"] == 0
    assert info["warmed_k_s"] == [6, 4, 3]
    assert info["rounds"] == len(info["k_s_per_round"])
    assert 0 < min(info["compared_anchor_share"])


# ------------------------------------- a family and a reader, as new files

COUNTED = ("make_dataset", "model_keys", "scale_teacher", "labeled_stack",
           "forward_flops", "train_flops")
# a second family, written as a file of the copied tree only: the CNN
# family's code, each of its six functions noting that it was called
SEEN_CALLS = """
SEEN = set()


def _noted(fn):
    def call(*args, **kw):
        SEEN.add(fn.__name__)
        return fn(*args, **kw)
    return call


for _name in {names!r}:
    globals()[_name] = _noted(globals()[_name])
"""
MASK_READER = """
def read(ctx):
    rounds = ctx.get("round_metrics")
    if not rounds:
        return None
    return 100.0 * sum(r["mask_rate"] for r in rounds) / len(rounds)
"""


@pytest.fixture(scope="module")
def plugged(tmp_path_factory):
    """A run of the tiny cell whose configuration names ``cnn_copy``, a
    family that is a new file in the copied tree, which also holds a new
    per-layer reader of the rounds' metrics; and the run's reader ctx."""
    register_tiny()
    root = make_tiny_root(tmp_path_factory.mktemp("plugged"))
    (root / "families/cnn_copy.py").write_text(
        (root / "families/cnn.py").read_text()
        + SEEN_CALLS.format(names=COUNTED))
    (root / "metrics/rounds.mask_rate.py").write_text(MASK_READER)
    path = root / "configs/tiny.json"
    path.write_text(json.dumps({**json.loads(path.read_text()),
                                "family": "cnn_copy"}))
    seen = {}
    with pytest.MonkeyPatch.context() as mp:
        metrics_for = harness.metrics_for

        def keep_ctx(name, ctx, trace, root_):
            seen.update(ctx)
            return metrics_for(name, ctx, trace, root_)

        mp.setattr(harness, "metrics_for", keep_ctx)
        result = run_tiny(root)
    return root, result, seen


def test_a_family_added_as_a_file_runs_the_tiny_cell_correct(plugged):
    root, r, ctx = plugged
    fam = harness.family(ctx["cfg"], root)
    assert Path(fam.__file__) == root / "families/cnn_copy.py"
    assert fam.SEEN == set(COUNTED)
    assert r["correct"] is True and r["failed"] == 0
    for name, c in r["checks"].items():
        assert 0 <= c["value"] <= c["limit"], name
    assert r["_info"]["compiles_in_window"] == 0


def test_a_reader_added_as_a_file_reads_the_rounds_metrics(plugged):
    root, r, ctx = plugged
    rounds = ctx["round_metrics"]
    assert len(rounds) == r["attempted"] == len(ctx["round_s"])
    assert set(rounds[0]) == {"f_s", "f_u", "mask_rate", "k_s", "test_acc"}
    assert all(math.isfinite(m["f_s"]) for m in rounds)
    mask = harness.metric_readers(root)["rounds.mask_rate"].read(ctx)
    assert mask == pytest.approx(
        100 * np.mean([m["mask_rate"] for m in rounds]))
    assert 0 <= mask <= 100


# ------------------------------------------------------------- refusals

def _run_py(cwd: Path, *args) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(cwd / "bench/run.py"), *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    p = _run_py(REPO, "--workload", "cnn-default", "--seed", "1",
                "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and p.stdout == ""
    assert "no TPU found" in p.stderr


def test_run_refuses_an_unknown_cell():
    p = _run_py(REPO, "--workload", "nope", "--seed", "1", "--seconds", "1")
    assert p.returncode != 0 and p.stdout == ""
    assert "no workload named 'nope'" in p.stderr


def test_run_refuses_without_the_program(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path, "--workload", "cnn-default", "--seed", "1",
                "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and p.stdout == ""
    assert "sources are missing" in p.stderr
