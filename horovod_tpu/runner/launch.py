"""``hvdrun`` — the horovodrun-style launcher.

Reference: /root/reference/horovod/runner/launch.py (CLI surface
:242-527, run_commandline :763), gloo_run.py (per-slot env injection +
SSH fan-out :226-271), mpi_run.py. TPU-native differences:

- rendezvous = our HTTP KV store + ``jax.distributed.initialize`` (the
  coordination service replaces MPI/Gloo bootstrap);
- one worker process per host VM drives all local chips (slots default 1);
  with as many local workers as the host has chips, worker k gets chip k
  and nothing else (``slot_env``); the launcher itself never initialises
  a JAX backend, because a process that has done so holds the chips;
- NIC discovery is a launcher-side route probe (runner/network.py) instead
  of the reference's SSH'd task-service intersection protocol — ICI
  topology is discovered by the TPU runtime itself, the launcher only has
  to pick the address workers dial for rendezvous/coordinator traffic
  (--network-interface overrides).

Usage:
    hvdrun -np 2 python train.py
    hvdrun -np 8 -H host1:4,host2:4 python train.py
    hvdrun -np 2 --min-np 1 --max-np 4 --host-discovery-script ./d.sh python train.py
"""

from __future__ import annotations

import argparse
import glob
import os
import shlex
import signal
import socket
import subprocess
import sys
import threading
import time
from typing import Optional

from ..common import env as env_schema
from .hosts import (HostInfo, SlotInfo, get_host_assignments,
                    hosts_from_allocation, parse_hostfile, parse_hosts)
from .http_server import RendezvousServer


def _free_port() -> int:
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


#: chips on a host -> the host's chip grid (libtpu's x,y,z bounds). Only
#: what has run: a v5e 2x2 host.
_HOST_CHIP_BOUNDS = {4: "2,2,1"}


def host_chips() -> int:
    """TPU chips attached to this host, counted from their device files:
    the launcher must not ask JAX, because a process that has initialised
    a backend holds the chips and its workers then fail or hang."""
    return len(glob.glob("/dev/vfio/[0-9]*") + glob.glob("/dev/accel[0-9]*"))


def _chip_env(slot: SlotInfo, coordinator: str, platforms: str) -> dict:
    """One process for each chip: the libtpu settings that give the worker
    with local rank k chip k and nothing else, as one process of a slice
    that spans the host, so ``jax.distributed`` assembles one world of
    ``local_size`` devices. Empty — nothing changes — for a single local
    worker (it drives every chip), for a job held to another platform,
    on a host without chips, and for remote hosts, whose chips the
    launcher cannot count."""
    if slot.local_size <= 1:
        return {}
    if platforms and "tpu" not in platforms.split(","):
        return {}
    chips = host_chips()
    if not chips:
        return {}
    from .network import is_local_host

    if not is_local_host(slot.hostname):
        return {}
    if (slot.cross_size != 1 or slot.local_size != chips
            or chips not in _HOST_CHIP_BOUNDS):
        raise ValueError(
            f"{slot.local_size} workers on a host with {chips} TPU chip(s): "
            "hvdrun gives each worker its own chip only for a single-host "
            "job with one worker per chip on a host of "
            f"{sorted(_HOST_CHIP_BOUNDS)} chips; use -np 1 to drive every "
            "chip from one process")
    # the slice-builder ports sit next to the coordinator's: every worker
    # of the job derives the same list from what it is already given
    base = int(coordinator.rsplit(":", 1)[1]) + 1
    return {
        "TPU_VISIBLE_CHIPS": str(slot.local_rank),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": _HOST_CHIP_BOUNDS[chips],
        "TPU_PROCESS_ADDRESSES": ",".join(
            f"localhost:{base + k}" for k in range(chips)),
        "TPU_PROCESS_PORT": str(base + slot.local_rank),
        "CLOUD_TPU_TASK_ID": str(slot.local_rank),
    }


def slot_env(slot: SlotInfo, rendezvous_addr: str, rendezvous_port: int,
             coordinator: str, extra_env: Optional[dict] = None) -> dict:
    """Per-slot env injection (reference gloo_run.py:65
    create_slot_env_vars + gloo_context.cc:136-192 consumption), plus the
    worker's chip when the host has one for each (``_chip_env``)."""
    e = dict(os.environ)
    # Workers must be able to import horovod_tpu even when the launcher runs
    # from a source checkout (python adds the *script* dir to sys.path, not
    # the launcher's cwd) — prepend our own import root.
    import horovod_tpu

    pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(horovod_tpu.__file__)))
    pythonpath = e.get("PYTHONPATH", "")
    if pkg_root not in pythonpath.split(os.pathsep):
        # append the separator only when there was a PYTHONPATH: a blanket
        # rstrip would also drop a user's meaningful trailing empty entry
        # (empty entry = cwd)
        e["PYTHONPATH"] = pkg_root + (os.pathsep + pythonpath
                                      if pythonpath else "")
    e.update({
        env_schema.HOROVOD_RANK: str(slot.rank),
        env_schema.HOROVOD_SIZE: str(slot.size),
        env_schema.HOROVOD_LOCAL_RANK: str(slot.local_rank),
        env_schema.HOROVOD_LOCAL_SIZE: str(slot.local_size),
        env_schema.HOROVOD_CROSS_RANK: str(slot.cross_rank),
        env_schema.HOROVOD_CROSS_SIZE: str(slot.cross_size),
        env_schema.HOROVOD_HOSTNAME: slot.hostname,
        env_schema.HOROVOD_GLOO_RENDEZVOUS_ADDR: rendezvous_addr,
        env_schema.HOROVOD_GLOO_RENDEZVOUS_PORT: str(rendezvous_port),
        env_schema.HOROVOD_TPU_COORDINATOR: coordinator,
        env_schema.HOROVOD_TPU_NUM_PROCESSES: str(slot.size),
        env_schema.HOROVOD_TPU_PROCESS_ID: str(slot.rank),
    })
    if extra_env:
        e.update(extra_env)
    e.update(_chip_env(slot, coordinator, e.get("JAX_PLATFORMS", "")))
    return e


def build_ssh_command(hostname: str, command: list[str], env: dict, *,
                      ssh_port: Optional[int] = None,
                      ssh_identity_file: Optional[str] = None) -> list[str]:
    """SSH fan-out command with env inlined (reference gloo_run
    get_remote_command). Shared by the static and elastic launchers."""
    env_str = " ".join(
        f"{k}={shlex.quote(v)}" for k, v in env.items()
        if k.startswith("HOROVOD_") or k in ("PATH", "PYTHONPATH"))
    ssh_args = ["ssh", "-o", "StrictHostKeyChecking=no"]
    if ssh_port:
        ssh_args += ["-p", str(ssh_port)]
    if ssh_identity_file:
        ssh_args += ["-i", ssh_identity_file]
    remote = f"cd {shlex.quote(os.getcwd())} && env {env_str} " \
             + " ".join(shlex.quote(c) for c in command)
    return ssh_args + [hostname, remote]


def _stream(prefix: str, pipe, out, tee_path: Optional[str] = None,
            tee_mode: str = "wb"):
    tee = open(tee_path, tee_mode) if tee_path else None
    try:
        for line in iter(pipe.readline, b""):
            out.write(f"[{prefix}]<stdout>: ".encode()
                      if out is sys.stdout.buffer
                      else f"[{prefix}]<stderr>: ".encode())
            out.write(line)
            out.flush()
            if tee is not None:
                tee.write(line)
                tee.flush()
    finally:
        if tee is not None:
            tee.close()


def start_output_threads(p, rank: int, output_filename: Optional[str],
                         first_incarnation: bool = True) -> list:
    """Start the rank-prefixed console streams for one worker, teeing
    into <output_filename>/rank.<rank>.{out,err} when set (fresh file on
    the first incarnation, append on elastic respawns). Returns the
    stream threads — join them after the worker exits so the file holds
    the full output."""
    threads = []
    for pipe, out, kind in ((p.stdout, sys.stdout.buffer, "out"),
                            (p.stderr, sys.stderr.buffer, "err")):
        tee = (os.path.join(output_filename, f"rank.{rank}.{kind}")
               if output_filename else None)
        t = threading.Thread(
            target=_stream,
            args=(str(rank), pipe, out, tee,
                  "wb" if first_incarnation else "ab"),
            daemon=True)
        t.start()
        threads.append(t)
    return threads


def launch_slots(command: list[str], slots: list[SlotInfo], *,
                 ssh_port: Optional[int] = None,
                 ssh_identity_file: Optional[str] = None,
                 extra_env: Optional[dict] = None,
                 verbose: bool = False,
                 output_filename: Optional[str] = None,
                 network_interface: Optional[str] = None) -> int:
    """Spawn one worker per slot (local exec or SSH for remote hosts),
    stream rank-prefixed output, kill the job on first failure
    (reference gloo_run.py:252-271). ``output_filename`` additionally
    tees each rank into <dir>/rank.<r>.{out,err} (reference horovodrun
    --output-filename)."""
    if output_filename:
        os.makedirs(output_filename, exist_ok=True)
    # mint (or reuse) the job secret BEFORE the server starts: the store
    # reads it from env, and slot_env's os.environ snapshot delivers it
    # to every worker (reference secret.py + gloo_run.py:65 injection)
    from .secret import get_or_mint_env_secret

    get_or_mint_env_secret()
    rendezvous = RendezvousServer()
    rendezvous.start()
    from .network import is_local_host, pick_coordinator_address

    remote = sorted({s.hostname for s in slots
                     if not is_local_host(s.hostname)})
    if not remote:
        addr = "127.0.0.1"
    else:
        # probe which local address routes to the workers (reference
        # get_common_interfaces, driver_service.py:218; redesigned as a
        # launcher-side route lookup — see runner/network.py)
        addr, _ = pick_coordinator_address(
            remote, iface_override=network_interface or os.environ.get(
                env_schema.HOROVOD_GLOO_IFACE))
    coordinator = f"{addr}:{_free_port()}"

    procs: list[subprocess.Popen] = []
    threads = []
    try:
        for slot in slots:
            e = slot_env(slot, addr, rendezvous.port, coordinator, extra_env)
            local = is_local_host(slot.hostname)
            if local:
                p = subprocess.Popen(command, env=e, stdout=subprocess.PIPE,
                                     stderr=subprocess.PIPE)
            else:
                p = subprocess.Popen(
                    build_ssh_command(slot.hostname, command, e,
                                      ssh_port=ssh_port,
                                      ssh_identity_file=ssh_identity_file),
                    stdout=subprocess.PIPE, stderr=subprocess.PIPE)
            procs.append(p)
            threads.extend(start_output_threads(p, slot.rank,
                                                output_filename))

        exit_code = 0
        alive = set(range(len(procs)))
        while alive:
            for i in list(alive):
                rc = procs[i].poll()
                if rc is not None:
                    alive.discard(i)
                    if rc != 0:
                        # first failure kills the job (gloo_run.py:263-271)
                        exit_code = rc
                        for j in alive:
                            procs[j].send_signal(signal.SIGTERM)
                        for j in alive:
                            try:
                                procs[j].wait(timeout=10)
                            except subprocess.TimeoutExpired:
                                procs[j].kill()
                        alive.clear()
                        break
            time.sleep(0.1)
        for t in threads:
            t.join(timeout=2)
        return exit_code
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        rendezvous.stop()


def make_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="hvdrun",
        description="Launch a horovod_tpu job (horovodrun equivalent).")
    p.add_argument("-np", "--num-proc", type=int, default=None)
    p.add_argument("-H", "--hosts", default=None,
                   help="host1:slots,host2:slots (default: localhost:np)")
    p.add_argument("--hostfile", default=None)
    p.add_argument("--from-allocation", action="store_true",
                   help="derive the host list from the scheduler "
                        "allocation's environment (LSB_DJOB_HOSTFILE / "
                        "LSB_MCPU_HOSTS / LSB_HOSTS / "
                        "SLURM_JOB_NODELIST+SLURM_TASKS_PER_NODE; "
                        "reference jsrun/LSF path, runner/js_run.py). "
                        "-np defaults to every allocated slot")
    p.add_argument("-p", "--ssh-port", type=int, default=None)
    p.add_argument("-i", "--ssh-identity-file", default=None)
    p.add_argument("--env", action="append", default=[],
                   help="KEY=VALUE to forward to workers (repeatable)")
    p.add_argument("--verbose", "-v", action="store_true")
    p.add_argument("--config-file", default=None,
                   help="YAML config mirroring CLI groups (reference "
                        "runner/common/util/config_parser.py)")
    # runtime knobs -> env (reference launch.py make_override_action)
    p.add_argument("--fusion-threshold-mb", type=int, default=None)
    p.add_argument("--cycle-time-ms", type=float, default=None)
    p.add_argument("--timeline-filename", default=None)
    p.add_argument("--timeline-mark-cycles", action="store_true")
    p.add_argument("--autotune", action="store_true")
    p.add_argument("--autotune-log-file", default=None)
    p.add_argument("--autotune-warmup-samples", type=int, default=None)
    p.add_argument("--autotune-steps-per-sample", type=int, default=None)
    p.add_argument("--autotune-bayes-opt-max-samples", type=int,
                   default=None)
    p.add_argument("--cache-capacity", type=int, default=None)
    p.add_argument("--no-stall-check", action="store_true")
    p.add_argument("--stall-check-warning-time-seconds", type=float,
                   default=None)
    p.add_argument("--stall-check-shutdown-time-seconds", type=float,
                   default=None)
    p.add_argument("--hierarchical-allreduce", action="store_true")
    p.add_argument("--hierarchical-allgather", action="store_true")
    p.add_argument("--output-filename", default=None,
                   help="directory for per-rank output files "
                        "rank.<r>.{out,err} (reference horovodrun "
                        "--output-filename); console streaming continues")
    p.add_argument("--network-interface", default=None,
                   help="NIC whose address workers dial for rendezvous/"
                        "coordinator traffic (reference horovodrun "
                        "--network-interface); default: probe the route "
                        "to each worker host")
    p.add_argument("--log-level", default=None)
    # elastic
    p.add_argument("--min-np", type=int, default=None)
    p.add_argument("--max-np", type=int, default=None)
    p.add_argument("--host-discovery-script", default=None)
    p.add_argument("--slots-per-host", type=int, default=1)
    p.add_argument("--check-build", action="store_true",
                   help="print framework/backend availability and exit "
                        "(reference horovodrun --check-build)")
    p.add_argument("command", nargs=argparse.REMAINDER)
    return p


def check_build() -> str:
    """Capability matrix (reference runner/launch.py check_build output
    shape: Available Frameworks / Controllers / Tensor Operations)."""

    def mark(flag: bool) -> str:
        return "[X]" if flag else "[ ]"

    def importable(mod: str) -> bool:
        import importlib.util

        return importlib.util.find_spec(mod) is not None

    from .._native import lib as native_lib

    lines = [
        "Horovod-TPU v" + __import__("horovod_tpu").__version__,
        "",
        "Available Frameworks:",
        f"    {mark(True)} JAX",
        f"    {mark(importable('tensorflow'))} TensorFlow",
        f"    {mark(importable('torch'))} PyTorch",
        f"    {mark(importable('keras'))} Keras",
        f"    {mark(importable('mxnet'))} MXNet",
        "",
        "Available Controllers:",
        f"    {mark(True)} KV (HTTP rendezvous)",
        f"    {mark(True)} XLA (compiled SPMD)",
        "",
        "Available Tensor Operations:",
        f"    {mark(True)} XLA/ICI collectives",
        f"    {mark(native_lib() is not None)} native C++ core",
        "",
        "Cluster Integrations:",
        f"    {mark(importable('pyspark'))} Spark",
        f"    {mark(importable('ray'))} Ray",
    ]
    return "\n".join(lines)


def _apply_config_file(args):
    if not args.config_file:
        return
    import yaml  # type: ignore

    with open(args.config_file) as f:
        cfg = yaml.safe_load(f) or {}
    for k, v in cfg.items():
        k = k.replace("-", "_")
        if getattr(args, k, None) in (None, False, []):
            setattr(args, k, v)


def _knob_env(args) -> dict:
    e = {}
    if args.fusion_threshold_mb is not None:
        e[env_schema.HOROVOD_FUSION_THRESHOLD] = str(args.fusion_threshold_mb << 20)
    if args.cycle_time_ms is not None:
        e[env_schema.HOROVOD_CYCLE_TIME] = str(args.cycle_time_ms)
    if args.timeline_filename:
        e[env_schema.HOROVOD_TIMELINE] = args.timeline_filename
    if args.timeline_mark_cycles:
        e[env_schema.HOROVOD_TIMELINE_MARK_CYCLES] = "1"
    if args.autotune:
        e[env_schema.HOROVOD_AUTOTUNE] = "1"
    if args.autotune_log_file:
        e[env_schema.HOROVOD_AUTOTUNE_LOG] = args.autotune_log_file
    if args.log_level:
        e[env_schema.HOROVOD_LOG_LEVEL] = args.log_level
    if args.autotune_warmup_samples is not None:
        e[env_schema.HOROVOD_AUTOTUNE_WARMUP_SAMPLES] = \
            str(args.autotune_warmup_samples)
    if args.autotune_steps_per_sample is not None:
        e[env_schema.HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE] = \
            str(args.autotune_steps_per_sample)
    if args.autotune_bayes_opt_max_samples is not None:
        e[env_schema.HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES] = \
            str(args.autotune_bayes_opt_max_samples)
    if args.cache_capacity is not None:
        e[env_schema.HOROVOD_CACHE_CAPACITY] = str(args.cache_capacity)
    if args.no_stall_check:
        e[env_schema.HOROVOD_STALL_CHECK_DISABLE] = "1"
    if args.stall_check_warning_time_seconds is not None:
        e[env_schema.HOROVOD_STALL_CHECK_TIME_SECONDS] = \
            str(args.stall_check_warning_time_seconds)
    if args.stall_check_shutdown_time_seconds is not None:
        e[env_schema.HOROVOD_STALL_SHUTDOWN_TIME_SECONDS] = \
            str(args.stall_check_shutdown_time_seconds)
    if args.hierarchical_allreduce:
        e[env_schema.HOROVOD_HIERARCHICAL_ALLREDUCE] = "1"
    if args.hierarchical_allgather:
        e[env_schema.HOROVOD_HIERARCHICAL_ALLGATHER] = "1"
    for kv in args.env:
        k, _, v = kv.partition("=")
        e[k] = v
    return e


def run_commandline(argv=None) -> int:
    args = make_parser().parse_args(argv)
    _apply_config_file(args)
    if args.check_build:
        print(check_build())
        return 0
    command = args.command
    if command and command[0] == "--":
        command = command[1:]
    if not command:
        print("hvdrun: no command given", file=sys.stderr)
        return 2

    if args.host_discovery_script or args.min_np or args.max_np:
        from ..elastic.driver import run_elastic

        if args.num_proc is None:
            args.num_proc = 1
        return run_elastic(command, args)

    if args.from_allocation:
        try:
            hosts = hosts_from_allocation(os.environ)
        except (ValueError, OSError) as e:
            print(f"hvdrun: {e}", file=sys.stderr)
            return 2
        if args.num_proc is None:
            args.num_proc = sum(h.slots for h in hosts)
    elif args.hostfile:
        hosts = parse_hostfile(args.hostfile)
    elif args.hosts:
        hosts = parse_hosts(args.hosts)
    else:
        hosts = [HostInfo("localhost", args.num_proc or 1)]
    if args.num_proc is None:
        args.num_proc = sum(h.slots for h in hosts) if args.hosts \
            or args.hostfile else 1
    try:
        slots = get_host_assignments(hosts, args.num_proc)
    except ValueError as e:
        print(f"hvdrun: {e}", file=sys.stderr)
        return 2
    return launch_slots(command, slots, ssh_port=args.ssh_port,
                        ssh_identity_file=args.ssh_identity_file,
                        extra_env=_knob_env(args), verbose=args.verbose,
                        output_filename=args.output_filename,
                        network_interface=args.network_interface)


def main():
    sys.exit(run_commandline())


def run(fn, args=(), kwargs=None, np: int = 1, extra_env: Optional[dict] = None):
    """Programmatic launch (reference horovod.run,
    runner/__init__.py:92): run ``fn`` in np local worker processes,
    return the list of results ordered by rank."""
    import tempfile

    try:  # closures/lambdas need cloudpickle; plain functions work either way
        import cloudpickle as pickle
    except ImportError:
        import pickle

    kwargs = kwargs or {}
    with tempfile.TemporaryDirectory() as td:
        payload = os.path.join(td, "fn.pkl")
        with open(payload, "wb") as f:
            pickle.dump((fn, args, kwargs), f)
        out_tpl = os.path.join(td, "out.{rank}.pkl")
        helper = (
            "import pickle,os,sys;"
            f"fn,a,k=pickle.load(open({payload!r},'rb'));"
            "r=fn(*a,**k);"
            f"pickle.dump(r,open({out_tpl!r}.format(rank=os.environ['HOROVOD_RANK']),'wb'))"
        )
        slots = get_host_assignments([HostInfo("localhost", np)], np)
        rc = launch_slots([sys.executable, "-c", helper], slots,
                          extra_env=extra_env)
        if rc != 0:
            raise RuntimeError(f"hvdrun job failed with exit code {rc}")
        return [pickle.load(open(out_tpl.format(rank=r), "rb")) for r in range(np)]


if __name__ == "__main__":
    main()
