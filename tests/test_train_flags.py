"""launch/train.py flag/env gating matrix.

``resolve_settings`` is the single point where ``--shard-clients`` /
``--prefetch`` / ``--num-processes`` meet their ``REPRO_*`` env
counterparts: flags always win, invalid combinations fail fast with a
clear SystemExit, and the result is a plain dataclass — so the whole
matrix is testable without touching JAX or spawning anything."""
import pytest

from repro.launch.train import RunSettings, build_parser, resolve_settings


def settings(argv, env=None):
    return resolve_settings(build_parser().parse_args(argv), env or {})


# ---------------------------------------------------------------------------
# flags override env
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("argv,env,shard,prefetch", [
    # no flag, no env: engine defaults (None = let the engine decide)
    ([], {}, None, None),
    # env alone drives both knobs
    ([], {"REPRO_SHARD_CLIENTS": "1"}, True, None),
    ([], {"REPRO_PREFETCH": "on"}, None, True),
    ([], {"REPRO_SHARD_CLIENTS": "0", "REPRO_PREFETCH": "false"},
     False, False),
    # flags win over contradicting env, both directions
    (["--shard-clients"], {"REPRO_SHARD_CLIENTS": "0"}, True, None),
    (["--no-shard-clients"], {"REPRO_SHARD_CLIENTS": "1"}, False, None),
    (["--prefetch"], {"REPRO_PREFETCH": "0"}, None, True),
    (["--no-prefetch"], {"REPRO_PREFETCH": "1"}, None, False),
    # independent knobs don't bleed into each other
    (["--prefetch"], {"REPRO_SHARD_CLIENTS": "on"}, True, True),
])
def test_flag_env_precedence(argv, env, shard, prefetch):
    s = settings(argv, env)
    assert s.shard_clients is shard
    assert s.prefetch is prefetch
    assert s.num_processes == 1 and not s.spawn


def test_bad_env_boolean_fails_fast():
    with pytest.raises(SystemExit, match="REPRO_SHARD_CLIENTS"):
        settings([], {"REPRO_SHARD_CLIENTS": "maybe"})
    with pytest.raises(SystemExit, match="REPRO_PREFETCH"):
        settings([], {"REPRO_PREFETCH": "2"})


# ---------------------------------------------------------------------------
# --num-processes / REPRO_NUM_PROCESSES topology resolution
# ---------------------------------------------------------------------------

def test_num_processes_flag_and_env():
    # flag alone: parent spawner (no process id yet), sharding implied
    s = settings(["--num-processes", "2"])
    assert s == RunSettings(shard_clients=True, prefetch=None,
                            num_processes=2, process_id=None,
                            coordinator=None, spawn=True)
    # env alone
    s = settings([], {"REPRO_NUM_PROCESSES": "2", "REPRO_PROCESS_ID": "1",
                      "REPRO_COORDINATOR": "127.0.0.1:7777"})
    assert (s.num_processes, s.process_id, s.coordinator, s.spawn) == \
        (2, 1, "127.0.0.1:7777", False)
    # flag overrides env
    s = settings(["--num-processes", "4", "--process-id", "3"],
                 {"REPRO_NUM_PROCESSES": "2", "REPRO_PROCESS_ID": "0"})
    assert (s.num_processes, s.process_id) == (4, 3)
    # a child with an id does not spawn
    assert not settings(["--num-processes", "2", "--process-id", "0"]).spawn


def test_num_processes_invalid_combos_fail_fast():
    with pytest.raises(SystemExit, match="must be >= 1"):
        settings(["--num-processes", "0"])
    with pytest.raises(SystemExit, match="out of range"):
        settings(["--num-processes", "2", "--process-id", "2"])
    with pytest.raises(SystemExit, match="process id only means"):
        settings(["--process-id", "0"])
    with pytest.raises(SystemExit, match="integer"):
        settings([], {"REPRO_NUM_PROCESSES": "two"})
    # multi-process contradicts an explicit vmapped-executor request ...
    with pytest.raises(SystemExit, match="client-sharded"):
        settings(["--num-processes", "2", "--no-shard-clients"])
    with pytest.raises(SystemExit, match="client-sharded"):
        settings(["--num-processes", "2"], {"REPRO_SHARD_CLIENTS": "0"})
    # ... and only the SemiSFL system has a multi-process path
    with pytest.raises(SystemExit, match="baseline"):
        settings(["--num-processes", "2", "--baseline", "semifl"])


def test_num_processes_implies_sharding():
    s = settings(["--num-processes", "2", "--process-id", "1"])
    assert s.shard_clients is True
    # explicit agreement is of course fine
    s = settings(["--num-processes", "2", "--process-id", "1",
                  "--shard-clients"])
    assert s.shard_clients is True


# ---------------------------------------------------------------------------
# --shard-model / REPRO_SHARD_MODEL
# ---------------------------------------------------------------------------

def test_shard_model_flag_env_and_default():
    assert settings([]).shard_model == 1
    assert settings([], {"REPRO_SHARD_MODEL": "2"}).shard_model == 2
    # flag wins over env
    assert settings(["--shard-model", "4"],
                    {"REPRO_SHARD_MODEL": "2"}).shard_model == 4
    # shard-model 1 is the replicated default: no sharding implied
    s = settings(["--shard-model", "1"])
    assert s.shard_model == 1 and s.shard_clients is None


def test_shard_model_implies_client_sharding():
    s = settings(["--shard-model", "2"])
    assert s.shard_model == 2 and s.shard_clients is True
    # explicit agreement is fine; composes with the fleet topology
    s = settings(["--shard-model", "2", "--num-processes", "2"])
    assert (s.shard_model, s.shard_clients, s.num_processes) == (2, True, 2)


def test_shard_model_invalid_combos_fail_fast():
    with pytest.raises(SystemExit, match="must be >= 1"):
        settings(["--shard-model", "0"])
    with pytest.raises(SystemExit, match="integer"):
        settings([], {"REPRO_SHARD_MODEL": "two"})
    with pytest.raises(SystemExit, match="model-sharded"):
        settings(["--shard-model", "2", "--no-shard-clients"])
    with pytest.raises(SystemExit, match="model-sharded"):
        settings(["--shard-model", "2"], {"REPRO_SHARD_CLIENTS": "0"})


def test_full_config_keeps_its_queue_and_controller():
    from repro.configs import get_config
    from repro.launch.train import train_config

    full = train_config("paper-vgg16", smoke=False, k_s=15, k_u=4).semisfl
    ref = get_config("paper-vgg16").semisfl
    assert (full.queue_len, full.observation_period,
            full.adaptation_window) == (2048, ref.observation_period,
                                        ref.adaptation_window)
    assert (full.k_s_init, full.k_u) == (15, 4)
    # the smoke rig keeps its short-run overrides
    smoke = train_config("paper-vgg16", smoke=True, k_s=15, k_u=4).semisfl
    assert (smoke.queue_len, smoke.observation_period,
            smoke.adaptation_window) == (512, 3, 3)


def test_prefetch_baseline_gate():
    with pytest.raises(SystemExit, match="phase stacks"):
        settings(["--prefetch", "--baseline", "semifl"])
    # env-driven prefetch trips the same gate
    with pytest.raises(SystemExit, match="phase stacks"):
        settings(["--baseline", "semifl"], {"REPRO_PREFETCH": "1"})
    # explicit OFF against a full-model baseline is allowed
    s = settings(["--no-prefetch", "--baseline", "semifl"],
                 {"REPRO_PREFETCH": "1"})
    assert s.prefetch is False
