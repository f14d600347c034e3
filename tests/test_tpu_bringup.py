"""``chip_smoke.py`` rehearsed at toy sizes on the CPU (one process, then
the four-chip path on four virtual devices), ``bench.py`` without a chip,
and two processes agreeing on the compile cache: the bring-up cases that
start processes. The in-process cases are in tests/test_bringup.py; the
chip's verdict on the real sizes is in tests/test_tpu_compile.py and, on
the chip itself, ``python chip_smoke.py``.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(extra)
    return env


def test_two_processes_agree_on_the_cache_dir(tmp_path):
    """The path is part of the cache key: a process that asks the helper
    and a launcher-spawned worker whose ``hvd.init()`` asks for it,
    started in different directories (a cwd, pid or time in the path
    would differ), must name the same one."""
    direct = ("from horovod_tpu.utils import compile_cache as c\n"
              "c.enable_compilation_cache()\n"
              "print('DIR', c.active_cache_dir())\n")
    worker = ("import horovod_tpu as hvd\n"
              "hvd.init()\n"
              "from horovod_tpu.utils import compile_cache as c\n"
              "print('DIR', c.active_cache_dir())\n")
    procs = [subprocess.Popen([sys.executable, "-c", code], cwd=cwd,
                              env=_env(**extra), stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True)
             for code, cwd, extra in ((direct, str(tmp_path), {}),
                                      (worker, "/", {"HOROVOD_RANK": "0"}))]
    outs = [p.communicate(timeout=120) for p in procs]
    assert all(p.returncode == 0 for p in procs), outs
    dirs = [next(ln for ln in out.splitlines() if ln.startswith("DIR"))
            for out, _ in outs]
    assert dirs == ["DIR " + os.path.join(REPO, ".jax_cache")] * 2


def test_bench_exits_nonzero_without_a_chip():
    p = subprocess.run([sys.executable, os.path.join(REPO, "bench.py"),
                        "--quick"], env=_env(), capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert "found none" in p.stderr
    assert '"metric"' not in p.stdout + p.stderr


# --- chip_smoke.py rehearsed -------------------------------------------------

def _smoke(*args, **env):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        env=_env(**env), capture_output=True, text=True, timeout=600)


def test_chip_smoke_refuses_to_start_without_a_tpu():
    p = _smoke()
    assert p.returncode == 2, p.stderr[-2000:]
    assert "JAX found no TPU" in p.stderr and "[smoke]" not in p.stdout


def test_chip_smoke_tiny_runs_every_phase_and_is_no_result():
    p = _smoke("--tiny")
    assert p.returncode == 3, (p.stdout[-2000:], p.stderr[-2000:])
    for phase in ("trainer:", "eager:", "kernel:"):
        assert f"[smoke] {phase}" in p.stdout, p.stdout[-2000:]
    assert '"ok"' not in p.stdout


def test_chip_smoke_four_chip_rehearsal_on_virtual_devices():
    p = _smoke("--chips", "4", "--tiny",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert p.returncode == 3, (p.stdout[-3000:], p.stderr[-2000:])
    # only the three four-chip phases, nothing from the one-chip run
    assert "[smoke] trainer:" not in p.stdout
    assert "[smoke] dp4:" in p.stdout and "[smoke] ring4:" in p.stdout
    assert p.stdout.count("two DistributedOptimizer eager steps match") == 4
    assert '"ok"' not in p.stdout
