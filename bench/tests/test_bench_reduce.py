"""The trace reduction: busy time as a union, idle share, device time per
operation by name, Pallas kernels found by their custom-call names, and
a trace recorded here read through ``jax.profiler.ProfileData``."""
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import reduce  # noqa: E402

# operation names as a v5e trace spells them (XLA Ops line)
FCFS_OP = (
    "%fcfs_scan_pallas.12 = (f32[2054,1,32]{2,1,0:T(1,128)S(1)}, f32[12,32]) "
    "custom-call(f32[2054,1,32] %copy.206), custom_call_target=\"tpu_custom_call\""
)
WHILE_OP = "%while.71 = (s32[], f32[32,12]) while((s32[], f32[32,12]) %tuple.343)"
FUSION_OP = "%fusion.224 = f32[65536]{0:T(1024)S(1)} fusion(f32[513] %g), kind=kCustom"


def event(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def line(name, events):
    return NS(name=name, events=events)


def fake_profile():
    """One device, two programs (the second overlapping the first), a
    while loop holding a fusion and the FCFS kernel, and host spans."""
    device = NS(name="/device:TPU:0", lines=[
        line("XLA Modules", [
            event("jit__fleet_stream_batched(161)", 100, 400),
            event("jit_other(7)", 450, 100),   # overlaps 450..500
            event("jit_later(9)", 800, 100),
        ]),
        line("XLA Ops", [
            event(WHILE_OP, 100, 300),
            event(FUSION_OP, 120, 110),
            event(FCFS_OP, 250, 40),
            event(FCFS_OP.replace(".12 ", ".13 "), 300, 60),
            event("%copy.3 = f32[4] copy(f32[4] %x)", 820, 50),
        ]),
    ])
    host = NS(name="/host:CPU", lines=[
        line("python3", [
            event("window", 0, 1000),
            event("fleet_call", 50, 520),
            event("unrelated", 600, 50),
        ]),
    ])
    return NS(planes=[NS(name="/host:metadata", lines=[]), device, host])


def test_union_counts_overlap_once():
    assert reduce.union_ns([(0, 10), (5, 15), (20, 30)]) == 25
    assert reduce.union_ns([(0, 10), (2, 3)]) == 10
    assert reduce.union_ns([]) == 0


def test_gaps_are_the_uncovered_stretches():
    assert reduce.gaps([(10, 20), (15, 30), (40, 50)], 0, 60) == [
        (0, 10), (30, 40), (50, 60)]


@pytest.mark.parametrize("text,op,kernel", [
    (FCFS_OP, "fcfs_scan_pallas.12", "fcfs_scan_pallas"),
    (FUSION_OP, "fusion.224", "fusion"),
    ("%gf256_matmul_pallas_batched.1 = u8[2,6,4194304] custom-call(), "
     "custom_call_target=\"tpu_custom_call\"",
     "gf256_matmul_pallas_batched.1", "gf256_matmul_pallas_batched"),
])
def test_op_and_kernel_names(text, op, kernel):
    assert reduce.op_name(text) == op
    assert reduce.kernel_of(op) == kernel


def test_self_time_subtracts_nested_ops():
    got = {n: ns for _, n, ns in reduce.self_times(
        [(0, 100, "outer"), (10, 20, "a"), (50, 30, "b"), (200, 5, "c")])}
    assert got == {"outer": 50, "a": 20, "b": 30, "c": 5}


def test_reduce_busy_idle_and_kernel_time():
    r = reduce.reduce_profile(fake_profile(), host_names={"window", "fleet_call"})
    assert r.window_s == pytest.approx(1000e-9)
    # programs 100..550 (overlap counted once) and 800..900
    assert r.busy_s == pytest.approx(550e-9)
    assert r.idle_share == pytest.approx(0.45)
    assert r.op_seconds("fcfs_scan_pallas") == pytest.approx(100e-9)
    while_key = "jit__fleet_stream_batched/while.71"
    assert r.op_s[while_key] == pytest.approx(90e-9)  # 300 less 110, 40, 60
    assert r.op_s["jit_later/copy.3"] == pytest.approx(50e-9)
    # the gap 0..100 lies in fleet_call; 550..800 and 900..1000 only in window
    assert r.idle_by_host["fleet_call"] == pytest.approx(100e-9)
    assert r.idle_by_host["window"] == pytest.approx(350e-9)
    b = r.breakdown()
    assert b["device_ops"][0][0] == "jit__fleet_stream_batched/fusion.224"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_reduce_reads_a_trace_recorded_here(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench_window"):
        for _ in range(3):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    r = reduce.reduce_dir(tmp_path, host_names={"bench_window"})
    assert r.window_s > 0
    # a CPU trace has no TPU plane: nothing counts as device busy and no
    # device gap is put down to a span
    assert r.n_devices == 0 and r.busy_s == 0.0
    assert r.idle_by_host == {}


def test_reduce_without_trace_file_is_an_error(tmp_path):
    with pytest.raises(FileNotFoundError):
        reduce.reduce_dir(tmp_path)
