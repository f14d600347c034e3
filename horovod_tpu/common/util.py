"""Small cross-framework helpers shared by the torch/TF/MXNet shims."""

from __future__ import annotations

import contextlib
import logging
import os
import tempfile

LOG = logging.getLogger("horovod_tpu")

# process umask, read once at import (single-threaded) — os.umask() is
# process-global and racy to query from concurrent writers
_UMASK = os.umask(0)
os.umask(_UMASK)


@contextlib.contextmanager
def atomic_tmp(path: str, mode: int | None = 0o666):
    """Yield a unique tmp filename next to ``path``; atomically commit it
    over ``path`` on clean exit, remove it on error.

    The single atomic-replace implementation for every concurrent writer
    in the runtime (store chunks, pickle checkpoints, the native-lib
    build): N launcher workers write the same artifact simultaneously, so
    tmp names must be per-call unique (a shared name lets one worker
    truncate the file another is mid-writing and makes the loser's
    ``os.replace`` fail with FileNotFoundError) and the tmp must live in
    the target's directory so the rename stays on one filesystem.
    ``mode`` restores plain-``open()`` permissions at commit (mkstemp
    creates 0600; shared stores are read across uids) — best-effort, and
    ``None`` keeps the tmp's mode.
    """
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=d, prefix=os.path.basename(path),
                               suffix=".tmp")
    os.close(fd)
    try:
        yield tmp
        if mode is not None:
            try:
                os.chmod(tmp, mode & ~_UMASK)
            except OSError:  # e.g. some CIFS/FUSE mounts — keep the write
                pass
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def atomic_write_bytes(path: str, data: bytes, mode: int | None = 0o666):
    """Concurrency-safe whole-file write via :func:`atomic_tmp`."""
    with atomic_tmp(path, mode=mode) as tmp:
        with open(tmp, "wb") as f:
            f.write(data)

_warned_64bit = False


def warn_64bit_narrowing(dtype) -> None:
    """Reference Horovod preserves MPI_DOUBLE/MPI_LONG on the wire
    (common/wire/message.fbs DataType); this runtime narrows 64-bit values
    to 32-bit (JAX runs x64-disabled — TPUs have no f64 ALUs). Silent
    precision loss is unacceptable for e.g. f64 statistics, so say it once
    per process."""
    global _warned_64bit
    if not _warned_64bit:
        _warned_64bit = True
        LOG.warning(
            "collective input dtype %s rides the wire as 32-bit (JAX x64 is "
            "disabled; TPUs have no float64 units). The caller dtype is "
            "restored on output but precision beyond 32 bits is lost. See "
            "docs/frameworks.md.", dtype)


def module_namespace(mod, **extra):
    """A SimpleNamespace copy of ``mod``'s public attributes with
    framework-specific additions grafted on — used by the shims to
    present ``hvd.elastic`` (etc.) with extra classes without mutating
    the shared module."""
    import types

    ns = types.SimpleNamespace(
        **{k: getattr(mod, k) for k in dir(mod) if not k.startswith("_")})
    for k, v in extra.items():
        setattr(ns, k, v)
    return ns
