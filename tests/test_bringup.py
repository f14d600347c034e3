"""What bringing the trainer up on the chip changed, as far as one CPU
process can show it: where the compile cache goes, which chip ``hvdrun``
gives each worker, and that no entry point hides a missing chip, a failed
phase, an unknown device or a failed ``jax.distributed.initialize``. The
cases that start processes (``chip_smoke.py`` rehearsed, ``bench.py``
without a chip) are in tests/test_tpu_bringup.py; the chip's own
compiler's verdict on the real sizes is in tests/test_tpu_compile.py.
"""

import os
import sys
import types

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


# --- compile cache: one helper, one rule ------------------------------------

@pytest.fixture
def config_updates(monkeypatch):
    """Record what the helper sets instead of setting it: the persistent
    cache must not come on for the rest of this test process."""
    from horovod_tpu.utils import compile_cache

    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.__setitem__(k, v))
    monkeypatch.setattr(compile_cache, "_ACTIVE_DIR", None)
    return calls


def test_cache_dir_from_env_is_not_set_in_code(monkeypatch, config_updates,
                                               tmp_path):
    from horovod_tpu.utils import compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "given"))
    assert compile_cache.enable_compilation_cache() is None
    assert "jax_compilation_cache_dir" not in config_updates
    assert not (tmp_path / "given").exists()  # JAX's to make, not ours
    # recorded all the same, for the memledger's hit/miss inference
    assert compile_cache.active_cache_dir() == str(tmp_path / "given")
    assert compile_cache.cache_entries() == -1
    (tmp_path / "given").mkdir()
    (tmp_path / "given" / "entry").write_text("x")
    assert compile_cache.cache_entries() == 1


def test_cache_dir_defaults_to_the_checkout(monkeypatch, config_updates):
    from horovod_tpu.utils import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("HOME", "/nonexistent")  # never the home directory
    assert compile_cache.enable_compilation_cache() is None
    want = os.path.join(REPO, ".jax_cache")
    assert config_updates["jax_compilation_cache_dir"] == want
    assert compile_cache.active_cache_dir() == want


# --- one process for each chip ----------------------------------------------

def _slot(local_rank, local_size, cross_size=1, hostname="localhost"):
    from horovod_tpu.runner.hosts import SlotInfo

    return SlotInfo(hostname, local_rank, local_size * cross_size,
                    local_rank, local_size, 0, cross_size)


def _tpu_vars(env):
    return {k: v for k, v in env.items()
            if k.startswith(("TPU_VISIBLE", "TPU_PROCESS", "TPU_CHIPS_PER_P",
                             "CLOUD_TPU_TASK"))}


@pytest.mark.parametrize("k", range(4))
def test_slot_env_gives_local_rank_k_chip_k(monkeypatch, k):
    from horovod_tpu.runner import launch

    monkeypatch.setattr(launch, "host_chips", lambda: 4)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    env = launch.slot_env(_slot(k, 4), "127.0.0.1", 1234, "127.0.0.1:40000")
    assert _tpu_vars(env) == {
        "TPU_VISIBLE_CHIPS": str(k),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "2,2,1",
        "TPU_PROCESS_ADDRESSES": "localhost:40001,localhost:40002,"
                                 "localhost:40003,localhost:40004",
        "TPU_PROCESS_PORT": str(40001 + k),
        "CLOUD_TPU_TASK_ID": str(k),
    }
    assert env["HOROVOD_LOCAL_RANK"] == str(k)


@pytest.mark.parametrize("case", ["no chips", "held to the cpu",
                                  "one worker drives every chip"])
def test_slot_env_changes_nothing_without_a_chip_each(monkeypatch, case):
    from horovod_tpu.runner import launch

    chips, platforms, size = {
        "no chips": (0, "", 4),
        "held to the cpu": (4, "cpu", 4),
        "one worker drives every chip": (4, "tpu,cpu", 1),
    }[case]
    if case != "no chips":  # the sandbox's own answer stands for that one
        monkeypatch.setattr(launch, "host_chips", lambda: chips)
    monkeypatch.setenv("JAX_PLATFORMS", platforms)
    env = launch.slot_env(_slot(0, size), "127.0.0.1", 1234,
                          "127.0.0.1:40000")
    assert _tpu_vars(env) == {}


def test_slot_env_refuses_a_worker_count_it_cannot_place(monkeypatch):
    from horovod_tpu.runner import launch

    monkeypatch.setattr(launch, "host_chips", lambda: 4)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(ValueError, match="2 workers on a host with 4"):
        launch.slot_env(_slot(0, 2), "127.0.0.1", 1234, "127.0.0.1:40000")


# --- no fallback -------------------------------------------------------------

def test_bench_failed_phase_fails_the_run(monkeypatch, capsys, tmp_path):
    import bench

    monkeypatch.setattr(sys, "argv", ["bench.py", "--quick"])
    monkeypatch.setattr(bench, "enable_compilation_cache", lambda: None)
    monkeypatch.setattr(bench, "_require_chip", lambda: None)
    monkeypatch.setattr(bench, "chip_peak_flops", lambda: 197e12)
    monkeypatch.setattr(bench, "bench_resnet", lambda *a, **k: 100.0)
    monkeypatch.setattr(bench, "bench_eager_allreduce", lambda *a, **k: 1.0)
    monkeypatch.setattr(bench, "_RESULT_FILE", str(tmp_path / "r.json"))

    def broken(*a, **k):
        raise RuntimeError("adasum phase broke")

    monkeypatch.setattr(bench, "bench_adasum", broken)
    with pytest.raises(RuntimeError, match="adasum phase broke"):
        bench.main()
    assert '"metric"' not in capsys.readouterr().out
    assert not (tmp_path / "r.json").exists()


@pytest.mark.parametrize("kind,peak", [
    ("TPU v5 lite", 197e12), ("TPU v5e", 197e12), ("TPU v5p", 459e12),
    ("TPU v4", 275e12), ("TPU v6 lite", 918e12),
    ("cpu", None), ("TPU v9", None), ("NVIDIA H100", None)])
def test_chip_peak_flops_knows_its_table_and_nothing_else(monkeypatch, kind,
                                                          peak):
    """A device that is not in the peaks table is an error, not v5e."""
    import bench

    monkeypatch.setattr(
        jax, "devices", lambda: [types.SimpleNamespace(device_kind=kind)])
    if peak is None:
        with pytest.raises(ValueError, match="no bf16 peak known"):
            bench.chip_peak_flops()
    else:
        assert bench.chip_peak_flops() == peak


def test_failed_distributed_init_raises_in_a_spawned_worker(monkeypatch):
    """A launcher-spawned worker whose jax.distributed.initialize fails
    must not carry on as a world of one."""
    from horovod_tpu.common import context as ctx_mod
    from horovod_tpu.common import env as env_schema

    monkeypatch.setenv(env_schema.HOROVOD_TPU_COORDINATOR, "127.0.0.1:1")
    monkeypatch.setenv(env_schema.HOROVOD_TPU_NUM_PROCESSES, "2")
    monkeypatch.setenv(env_schema.HOROVOD_TPU_PROCESS_ID, "1")

    def refuse(**kw):
        raise RuntimeError("coordinator unreachable")

    monkeypatch.setattr(jax.distributed, "initialize", refuse)
    with pytest.raises(RuntimeError, match="coordinator unreachable"):
        ctx_mod._maybe_init_distributed()



# --- the kernel's tiling rule -------------------------------------------------

@pytest.mark.parametrize("sq,sk,bq,bk,on_tpu,want", [
    (2048, 2048, 512, 512, True, True),    # the LM's shapes
    (2048, 8192, 512, 1024, True, True),   # q and k/v lengths differ
    (200, 200, 512, 512, True, True),      # one whole-sequence block each
    (2112, 2112, 512, 512, True, False),   # 512 does not divide 2112
    (256, 256, 64, 64, True, False),       # 64 is no lane multiple...
    (256, 256, 64, 64, False, True),       # ...which interpret mode allows
    (2048, 256, 512, 64, True, False),     # every block has to tile
    (100, 100, 8, 8, False, False),        # 8 does not divide 100 anywhere
])
def test_kernel_tiles(sq, sk, bq, bk, on_tpu, want):
    """One rule for ``_flash_fwd``'s refusal and ``sp._auto_flash``'s
    choice: blocks divide the sequences, and on TPU each is a multiple of
    128 or the whole sequence (what the chip's compiler accepts,
    tests/test_tpu_compile.py)."""
    from horovod_tpu.ops.pallas.flash_attention import kernel_tiles

    assert kernel_tiles(sq, sk, bq, bk, lane_aligned=on_tpu) is want


def test_flash_fwd_names_the_rule_when_it_refuses():
    import importlib

    import jax.numpy as jnp

    F = importlib.import_module("horovod_tpu.ops.pallas.flash_attention")
    x = jnp.zeros((1, 100, 16), jnp.float32)
    with pytest.raises(ValueError, match="multiple of 128 or the whole"):
        F._flash_fwd(x, x, x, True, 8, 8)
