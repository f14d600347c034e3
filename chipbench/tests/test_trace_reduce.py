"""The reduction from a profiler trace to numbers: on a trace recorded
on a v5e (benchmarks/chip_evidence_r5/vm.xplane.pb, copied to data/) and
on a hand-made plane whose numbers are known exactly."""

import os

import pytest
from jax.profiler import ProfileData

from chipbench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
US = 1_000_000  # picoseconds in a microsecond


def test_opcode_and_class_of_hlo_text():
    fusion = ("%convolution_add_fusion.12 = bf16[256,56,56,64]{3,2,1,0:T(8,128)"
              "(2,1)} fusion(bf16[256,56,56,64]{3,2,1,0:T(8,128)(2,1)S(1)} "
              "%copy-done), kind=kOutput, calls=%fused_computation.12")
    start = ("%all-reduce-start.1 = (f32[25557032]{0:T(1024)}, f32[25557032]"
             "{0:T(1024)}) all-reduce-start(f32[25557032]{0:T(1024)} %x), "
             "replica_groups={{0,1,2,3}}, to_apply=%add")
    assert tr.opcode_of(fusion) == "fusion"
    assert tr.op_class(fusion) == "fusion.kOutput:convolution_add_fusion"
    assert tr.opcode_of(start) == "all-reduce-start"
    assert tr.op_class(start) == "all-reduce-start"
    assert tr.is_collective("all-reduce-start")
    assert tr.is_collective("collective-permute-done")
    assert not tr.is_collective("fusion")
    assert tr.opcode_of("%fusion.3") == "fusion"  # a bare name


def test_interval_arithmetic():
    u = tr.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert u == [(0, 3), (5, 8)]
    assert tr.length(u) == 6
    assert tr.overlap(u, [(2, 6), (7.5, 10)]) == 1 + 1 + 0.5
    assert tr.gaps(u, -1, 10) == [(-1, 0), (3, 5), (8, 10)]


def test_recorded_v5e_trace():
    """Seven programs on one chip; the one that took most time ran twice."""
    out = tr.reduce_file(os.path.join(DATA, "vm.xplane.pb"))
    (dev,) = out["devices"]
    assert dev["plane"] == "/device:TPU:0"
    assert dev["step_module"] == "jit__lambda(7162324978537466976)"
    assert dev["steps"] == 2
    assert dev["step_ms"] == pytest.approx([0.015823, 0.012632], abs=1e-6)
    assert dev["window_s"] == pytest.approx(457.220e-6, abs=1e-9)
    # two fusions, two copy-done waits, two copy-starts; the first async
    # copy (3.230 us) runs under nothing else, the second under its start
    assert dev["seconds_by_class"] == pytest.approx(
        {"fusion.kOutput": 12.588e-6, "copy-done": 3.218e-6,
         "copy-start": 26e-9},
        abs=1e-9)
    assert sorted(i["count"] for i in dev["instructions"].values()) \
        == [1, 2, 2]  # five events of three distinct instructions
    assert dev["busy_s"] == pytest.approx(15.836e-6, abs=1e-9)
    assert dev["collective_s"] == 0 and dev["exposed_collective_s"] == 0
    assert dev["idle_seconds_by_cause"] == pytest.approx(
        {"between programs, host in no span of the loop": 428.765e-6,
         "inside a program": 12.619e-6}, abs=1e-9)


def event(meta: int, start_us: float, dur_us: float) -> str:
    return (f"events {{ metadata_id: {meta} offset_ps: {int(start_us * US)} "
            f"duration_ps: {int(dur_us * US)} }}\n")


NAMES = {
    1: "jit_wrapped(1)",
    2: "%fusion.1 = f32[8]{0:T(256)} fusion(f32[8]{0:T(256)} %p), "
       "kind=kLoop, calls=%fused_computation.1",
    3: "%all-reduce-start.1 = f32[8]{0:T(256)} all-reduce-start(f32[8]"
       "{0:T(256)} %fusion.1), replica_groups={{0,1,2,3}}, to_apply=%add",
    4: "%convolution_add_fusion.2 = bf16[8,8]{1,0:T(8,128)(2,1)} fusion("
       "bf16[8,8]{1,0:T(8,128)(2,1)} %q), kind=kOutput, "
       "calls=%fused_computation.2",
    5: "%all-reduce-done.1 = f32[8]{0:T(256)} all-reduce-done(f32[8]"
       "{0:T(256)} %all-reduce-start.1)",
    6: "%all-gather.3 = f32[32]{0:T(256)} all-gather(f32[8]{0:T(256)} "
       "%all-reduce-done.1), dimensions={0}",
    7: "%copy.4 = f32[32]{0:T(256)} copy(f32[32]{0:T(256)} %all-gather.3)",
    8: "chipbench.wait",
    9: "chipbench.dispatch",
    10: "some other host span",
}


def hand_made_profile() -> ProfileData:
    """Three 800 us steps 1000 us apart on one chip. In each: a fusion
    (0-300), an asynchronous all-reduce from 300 to 600 under which a
    convolution fusion runs from 320 to 500 and whose done waits from
    500 to 600, an exposed synchronous all-gather (600-700), a copy
    (700-800). The host waits, then dispatches, in the first gap, and
    dispatches through most of the second."""
    modules = ops = async_ops = ""
    for k in range(3):
        t = 1000.0 * k
        modules += event(1, t, 800)
        ops += (event(2, t, 300) + event(3, t + 300, 1)
                + event(4, t + 320, 180) + event(5, t + 500, 100)
                + event(6, t + 600, 100) + event(7, t + 700, 100))
        async_ops += event(3, t + 300, 300)
    host = (event(8, 800, 150) + event(9, 950, 50) + event(9, 1800, 190)
            + event(10, 0, 3000))
    metadata = "".join(
        f'event_metadata {{ key: {k} value {{ id: {k} name: "{v}" }} }}\n'
        for k, v in NAMES.items())
    return ProfileData.from_text_proto(f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0 {modules} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0 {ops} }}
  lines {{ id: 3 name: "Async XLA Ops" timestamp_ns: 0 {async_ops} }}
  {metadata} }}
planes {{ id: 2 name: "/device:TPU:1" }}
planes {{ id: 3 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0 {host} }}
  {metadata} }}
""")


def test_hand_made_plane_with_overlapped_and_exposed_collective():
    out = tr.reduce_profile(hand_made_profile())
    (dev,) = out["devices"]  # nothing ran on TPU:1, so it is left out
    assert dev["step_module"] == "jit_wrapped(1)"
    assert dev["steps"] == 3  # fewer than six: no step is trimmed
    assert dev["step_ms"] == pytest.approx([0.8, 0.8, 0.8])
    assert dev["window_s"] == pytest.approx(2800e-6)
    assert dev["busy_s"] == pytest.approx(2400e-6)
    # per step: all-reduce 300-600 and all-gather 600-700 = 400 us, of
    # which the convolution fusion hides 180
    assert dev["collective_s"] == pytest.approx(3 * 400e-6)
    assert dev["exposed_collective_s"] == pytest.approx(3 * 220e-6)
    assert dev["seconds_by_class"] == pytest.approx({
        "fusion.kLoop": 900e-6,
        "fusion.kOutput:convolution_add_fusion": 540e-6,
        "all-reduce-done": 300e-6, "all-gather": 300e-6, "copy": 300e-6,
        "all-reduce-start": 3e-6})
    assert dev["idle_seconds_by_cause"] == pytest.approx({
        "between programs, host in chipbench.wait": 200e-6,
        "between programs, host in chipbench.dispatch": 200e-6})
    # every instruction with its whole HLO text, for a reader that counts
    # one kernel's operations and bytes from its shapes
    assert dev["instructions"][NAMES[4]] == pytest.approx(
        {"count": 3, "seconds": 540e-6})
    assert len(dev["instructions"]) == 6
    # only the result line's breakdown is cut
    cut = tr.breakdown(dev, n=2)
    assert cut["device_ops"] == [
        ["fusion.kLoop", pytest.approx(900e-6)],
        ["fusion.kOutput:convolution_add_fusion", pytest.approx(540e-6)]]
    assert len(cut["idle_gaps"]) == 2
    assert len(tr.breakdown(dev)["device_ops"]) == 6


def test_layer_metric_readers_on_the_hand_made_plane():
    from chipbench import run

    reduced = tr.reduce_profile(hand_made_profile())
    host = {"compile_s": 12.5, "dispatch_s": [0.001, 0.003, 0.002]}
    about = {"chips": 1, "peak_flops_per_s": 100e12,
             "train_flops_per_step_per_chip": 20e9}
    want = {"compile_s": 12.5, "dispatch_ms": 2.0, "device_step_ms": 0.8,
            "busy_mfu_pct": 25.0, "collective_ms": 0.4,
            "exposed_collective_ms": 0.22,
            "device_idle_pct": 100 * 400 / 2800}
    for name, value in want.items():
        reader = run.load_module("layer_metrics", name)
        assert reader.read(reduced, host, about) == pytest.approx(value), name
    # a reader that finds nothing to read returns nothing
    for name in want:
        reader = run.load_module("layer_metrics", name)
        assert reader.read({"devices": []}, {}, about) is None, name


def test_steady_window_drops_the_profilers_own_stall():
    """Six steps or more: the first two and the last are left out."""
    modules = ops = ""
    starts = [0, 5000, 6000, 7000, 8000, 9000, 15000]  # stalls at both ends
    for t in starts:
        modules += event(1, t, 800)
        ops += event(2, t, 800)
    metadata = "".join(
        f'event_metadata {{ key: {k} value {{ id: {k} name: "{v}" }} }}\n'
        for k, v in NAMES.items())
    profile = ProfileData.from_text_proto(f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0 {modules} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0 {ops} }}
  {metadata} }}""")
    (dev,) = tr.reduce_profile(profile)["devices"]
    assert dev["steps"] == 4
    assert dev["window_s"] == pytest.approx(3800e-6)  # 6000 .. 9800
    assert dev["busy_s"] == pytest.approx(3200e-6)
