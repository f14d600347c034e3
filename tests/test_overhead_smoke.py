"""The nine ``benchmarks/*_overhead.py`` A/A harnesses, smoke-tested.

A smoke says that a harness still imports, drives its cycles with the
subsystem off and on, and leaves the process with the subsystem's
default (absent) handle. It reads no clock: the 2% gates these
harnesses exist for are the ``slow``-marked ``*_benchguard`` tests,
over full runs on a quiet machine.
"""

import importlib
import importlib.util
import os

import pytest

from horovod_tpu.ops import collectives as C

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: harness, its measure function, the module and getter of the handle it
#: must put back (autotune keeps none), and what the "on" side must
#: report: keys above a floor, keys at a value
HARNESSES = [
    ("anatomy_overhead", "measure_anatomy",
     "horovod_tpu.utils.anatomy", "get_profiler", {}, {}),
    ("async_ckpt_overhead", "measure_async_ckpt",
     "horovod_tpu.utils.async_ckpt", "get_checkpointer",
     # the on config reports the snapshot-copy budget it measured
     {"snapshot_copy_s": 0.0, "shard_bytes": 0, "shard_write_s": 0.0}, {}),
    ("autotune_overhead", "measure_autotune", None, None, {}, {}),
    ("flightrec_overhead", "measure_flightrec",
     "horovod_tpu.utils.flightrec", "get_recorder", {}, {}),
    ("health_overhead", "measure_health",
     "horovod_tpu.utils.health", "get_engine", {}, {}),
    ("megaplan_overhead", "measure_megaplan",
     "horovod_tpu.ops.megaplan", "get_manager", {},
     # the timed cycles rode one captured schedule, with no negotiation
     {"captures": 1, "replay_hit_rate": 1.0, "negotiate_share": 0.0}),
    ("memledger_overhead", "measure_memledger",
     "horovod_tpu.utils.memledger", "get_ledger",
     # the on-run's compile accounting recorded the rebuild
     {"compiles": 0, "plan_cache_program_bytes": 0}, {}),
    ("perfledger_overhead", "measure_perfledger",
     "horovod_tpu.utils.perfledger", "get_ledger", {}, {}),
    ("trace_overhead", "measure_tracing",
     "horovod_tpu.utils.tracing", "get_tracer", {}, {}),
]


@pytest.mark.parametrize(
    "harness,measure,handle_module,getter,more_than,equal_to", HARNESSES,
    ids=[h[0] for h in HARNESSES])
def test_overhead_microbench_smoke(harness, measure, handle_module, getter,
                                   more_than, equal_to):
    spec = importlib.util.spec_from_file_location(
        f"_{harness}_smoke",
        os.path.join(REPO, "benchmarks", harness + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    try:
        off = getattr(mod, measure)(False, cycles=8, warmup=3)
        on = getattr(mod, measure)(True, cycles=8, warmup=3)
    finally:
        C.clear_eager_cache()  # drop plans built under the bench's states
    assert off["cycles"] == on["cycles"] == 8
    if getter is not None:  # the harness restored the default
        assert getattr(importlib.import_module(handle_module),
                       getter)() is None
    assert "HOROVOD_MEGAPLAN" not in os.environ  # nor left its switch set
    for key, floor in more_than.items():
        assert on[key] > floor, (key, on[key])
    assert {key: on[key] for key in equal_to} == equal_to
