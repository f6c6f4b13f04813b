import numpy as np
import pytest

# NOTE: do NOT set xla_force_host_platform_device_count here — smoke tests
# and benches must see the host's real (1) device; only dryrun.py forces
# 512 placeholder devices (and only in its own process).


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "tpu: needs real TPU hardware (Mosaic-compiled Pallas); such "
        "tests request the `tpu` fixture, which skips them elsewhere")
    if not config.pluginmanager.hasplugin("timeout"):
        # tests annotate explicit caps; without pytest-timeout installed
        # the marker is inert but must still be known
        config.addinivalue_line(
            "markers",
            "timeout(seconds): per-test wall-clock cap (enforced by "
            "pytest-timeout where installed — CI always installs it)")


def pytest_collection_modifyitems(config, items):
    # Per-test wall-clock cap via pytest-timeout (CI installs it; locally
    # optional): a deadlocked prefetch worker or a hung 8-device
    # subprocess job fails fast instead of stalling the whole run.  The
    # in-test subprocess timeouts are tighter (<= 600s), so 900s only
    # fires when something is truly wedged.
    if config.pluginmanager.hasplugin("timeout"):
        for item in items:
            if item.get_closest_marker("timeout") is None:
                item.add_marker(pytest.mark.timeout(900))


@pytest.fixture
def tpu():
    """Skip unless JAX's default backend is a TPU.  Decided when a test
    that asks for it runs, never while collecting: each collecting worker
    would otherwise initialize a backend, and on a TPU host claim the
    chip."""
    from repro.compat import is_tpu
    if not is_tpu():
        pytest.skip("requires TPU (jax default backend is not 'tpu'; "
                    "compiled-Pallas path untestable here)")


@pytest.fixture
def rng():
    return np.random.RandomState(0)


@pytest.fixture
def no_implicit_transfers():
    """Run the test under ``jax.transfer_guard("disallow")``: every
    IMPLICIT host->device transfer raises instead of silently happening —
    jit called on numpy args (a forgotten device_put of batch data),
    eager ops mixing host constants with device arrays (``state.round +
    1`` once per round), integer indexing of device stacks (``xs[i]``
    commits the index constant).

    Explicit transfers — ``jax.device_put``, ``jnp.asarray(np_val)``,
    ``jax.device_get`` — stay legal: the repo's hot-path contract is that
    every transfer must be visible at the call site (engine's ``_host``)
    so a sync regression can be grepped for, which is also why the static
    twin of this net (reprolint RL002) checks the same call patterns.

    Two scope caveats baked into the design:

      * test SETUP legitimately builds constants (``PRNGKey``,
        ``jnp.zeros`` queue init) — guarded tests wrap their setup in a
        short ``jax.transfer_guard("allow")`` block, keeping the round
        loop itself under the strict net;
      * the guard is thread-local, so the prefetch worker thread (whose
        whole job is device transfer) is unaffected — its safety is
        covered by reprolint RL003's call-graph rule instead.
    """
    import jax
    with jax.transfer_guard("disallow"):
        yield
