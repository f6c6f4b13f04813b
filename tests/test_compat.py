"""The JAX API boundary (``repro.compat``) against the installed JAX, and
the kernel backend knob."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.kernels import dispatch


# ---------------------------------------------------------------------------
# shard_map resolution
# ---------------------------------------------------------------------------

def test_resolve_shard_map_new_api_check_vma(monkeypatch):
    # check_vma is forwarded when given and left to JAX's default otherwise
    calls = []

    def fake(f, *, mesh, in_specs, out_specs, **kw):
        calls.append(kw)
        return f

    monkeypatch.setattr(jax, "shard_map", fake)
    compat.shard_map(abs, mesh=None, in_specs=(), out_specs=(),
                     check_vma=False)
    compat.shard_map(abs, mesh=None, in_specs=(), out_specs=())
    assert calls == [{"check_vma": False}, {}]


def test_shard_map_wrapper_runs_on_installed_jax():
    mesh = compat.make_mesh((1,), ("d",),
                            axis_types=(compat.AxisType.Auto,))
    out = compat.shard_map(
        lambda x: x * 2, mesh=mesh,
        in_specs=(jax.sharding.PartitionSpec(),),
        out_specs=jax.sharding.PartitionSpec(),
        check_vma=False)(jnp.arange(4.0))
    np.testing.assert_allclose(out, 2.0 * np.arange(4.0))


# ---------------------------------------------------------------------------
# make_mesh / AxisType
# ---------------------------------------------------------------------------

def test_make_mesh_passes_axis_types_on_new_signature(monkeypatch):
    calls = {}

    def fake_make(axis_shapes, axis_names, axis_types=None, *, devices=None):
        calls.update(axis_types=axis_types, devices=devices)
        return "mesh"

    monkeypatch.setattr(jax, "make_mesh", fake_make)
    types_ = (compat.AxisType.Auto, compat.AxisType.Auto)
    assert compat.make_mesh((2, 2), ("a", "b"), axis_types=types_,
                            devices="devs") == "mesh"
    assert calls == {"axis_types": types_, "devices": "devs"}


def test_axis_type_has_auto_member():
    assert hasattr(compat.AxisType, "Auto")


def test_make_mesh_real_jax_single_device():
    mesh = compat.make_mesh((1, 1), ("data", "model"),
                            axis_types=(compat.AxisType.Auto,) * 2)
    assert mesh.axis_names == ("data", "model")


# ---------------------------------------------------------------------------
# use_mesh
# ---------------------------------------------------------------------------

def test_use_mesh_prefers_set_mesh(monkeypatch):
    entered = []

    class _Cm:
        def __init__(self, mesh):
            entered.append(mesh)

        def __enter__(self):
            entered.append("enter")
            return self

        def __exit__(self, *a):
            entered.append("exit")
            return False

    monkeypatch.setattr(jax, "set_mesh", _Cm)
    with compat.use_mesh("mesh-object") as m:
        assert m == "mesh-object"
        assert entered == ["mesh-object", "enter"]
    assert entered == ["mesh-object", "enter", "exit"]


def test_use_mesh_bare_setter_is_undone_on_exit():
    mesh = compat.make_mesh((1,), ("d",))
    with compat.use_mesh(mesh):
        assert jax.sharding.get_abstract_mesh().axis_names == ("d",)
    assert jax.sharding.get_abstract_mesh().axis_names == ()


def test_use_mesh_real_jax():
    mesh = compat.make_mesh((1,), ("d",))
    with compat.use_mesh(mesh) as m:
        assert m is mesh
        # jit under the ambient mesh still works
        assert float(jax.jit(lambda x: x + 1)(jnp.float32(1.0))) == 2.0


# ---------------------------------------------------------------------------
# pallas compiler params
# ---------------------------------------------------------------------------

def test_pallas_compiler_params_real_jax():
    got = compat.pallas_compiler_params(
        dimension_semantics=("parallel", "arbitrary"))
    assert isinstance(got, compat.pltpu.CompilerParams)
    assert tuple(got.dimension_semantics) == ("parallel", "arbitrary")


# ---------------------------------------------------------------------------
# cost_analysis
# ---------------------------------------------------------------------------

def test_cost_analysis_real_jax():
    compiled = jax.jit(lambda x: x @ x).lower(
        jnp.ones((8, 8), jnp.float32)).compile()
    cost = compat.cost_analysis(compiled)
    assert isinstance(cost, dict)
    assert cost.get("flops", 0.0) > 0.0


# ---------------------------------------------------------------------------
# kernel backend knob
# ---------------------------------------------------------------------------

def test_backend_env_knob(monkeypatch):
    monkeypatch.setattr(dispatch, "_override", None)
    for value in ("ref", "interpret", "pallas", "auto"):
        monkeypatch.setenv(dispatch.ENV_VAR, value)
        assert dispatch.get_backend() == value
    monkeypatch.delenv(dispatch.ENV_VAR)
    assert dispatch.get_backend() == "auto"


def test_backend_unknown_value_is_a_clear_error(monkeypatch):
    monkeypatch.setattr(dispatch, "_override", None)
    monkeypatch.setenv(dispatch.ENV_VAR, "cuda")
    with pytest.raises(ValueError) as err:
        dispatch.get_backend()
    msg = str(err.value)
    assert "cuda" in msg and "REPRO_KERNEL_BACKEND" in msg
    for valid in dispatch.VALID_BACKENDS:
        assert valid in msg


def test_backend_auto_resolves_to_ref_on_cpu(monkeypatch):
    monkeypatch.setattr(dispatch, "_override", None)
    monkeypatch.delenv(dispatch.ENV_VAR, raising=False)
    monkeypatch.delenv("REPRO_PALLAS_COMPILE", raising=False)
    expected = "pallas" if compat.is_tpu() else "ref"
    assert dispatch.resolve() == expected


def test_backend_context_manager_restores(monkeypatch):
    monkeypatch.setattr(dispatch, "_override", None)
    with dispatch.backend("ref"):
        assert dispatch.get_backend() == "ref"
        assert dispatch.resolve() == "ref"
    assert dispatch.get_backend() == "auto"


def test_dispatch_routes_per_backend(monkeypatch):
    seen = []
    dispatch.register(
        "_test_kernel",
        ref=lambda x: seen.append("ref") or x,
        pallas=lambda x, interpret: seen.append(
            "interpret" if interpret else "pallas") or x)
    try:
        dispatch.call("_test_kernel", 1, backend="ref")
        dispatch.call("_test_kernel", 1, backend="interpret")
        dispatch.call("_test_kernel", 1, backend="pallas")
        assert seen == ["ref", "interpret", "pallas"]
    finally:
        dispatch._REGISTRY.pop("_test_kernel")


def test_dispatch_supports_predicate_forces_ref():
    seen = []
    dispatch.register(
        "_test_small", ref=lambda x: seen.append("ref"),
        pallas=lambda x, interpret: seen.append("pallas"),
        supports=lambda x: False)
    try:
        dispatch.call("_test_small", 1, backend="interpret")
        assert seen == ["ref"]
    finally:
        dispatch._REGISTRY.pop("_test_small")


def test_dispatch_unknown_kernel_is_a_clear_error():
    with pytest.raises(KeyError) as err:
        dispatch.call("no_such_kernel", 1)
    assert "no_such_kernel" in str(err.value)


def test_all_five_kernel_modules_are_dispatched():
    # ops.py registers every kernel on import
    import repro.kernels  # noqa: F401
    assert set(dispatch.registered()) >= {
        "clustering_loss", "flash_attention", "mamba2_scan", "slstm_scan"}
