"""The trace reduction: busy union, idle share, per-op time and the
attribution of idle gaps to the harness's host spans."""
import tinyroot

import glob

import pytest

from benchlib import readers, trace
from benchlib.trace import Op, Trace

MS = 1_000_000      # ns


M, B, N = 8, 16, 32     # memory vectors, batch, signals

KERNEL = ("%similarity.1 = f32[8,16]{1,0} custom-call(f32[8,32]{1,0} %x, "
          "f32[16,32]{1,0} %y, f32[8,1]{1,0} %x2, f32[1,16]{1,0} %y2), "
          'custom_call_target="tpu_custom_call"')
SCAN = ("%while.3 = (s32[], f32[32]{0}, f32[32]{0}, pred[16,32]{1,0}) "
        "while((s32[], f32[32]{0}, f32[32]{0}, pred[16,32]{1,0}) %tuple)")


def small_trace():
    """A 10 ms window: a kernel 1-3 ms, a fusion 2-4 ms (overlapping it), a
    scan 6-7 ms; host spans h2d 0-1, estimate 1-5, sprt 5-9."""
    ops = [Op(0, "%similarity.1", "jit_estimate", "jit_estimate " + KERNEL,
              1 * MS, 3 * MS),
           Op(0, "%fusion.2", "jit_estimate",
              "jit_estimate %fusion.2 = f32[8,16]{1,0} fusion(...)",
              2 * MS, 4 * MS),
           Op(0, "%while.3", "jit_scan", "jit_scan " + SCAN, 6 * MS, 7 * MS)]
    spans = [("bench.window", 0, 10 * MS), ("bench.h2d", 0, 1 * MS),
             ("bench.estimate", 1 * MS, 5 * MS), ("bench.sprt", 5 * MS, 9 * MS)]
    return Trace(ops=ops, spans=spans, window=(0, 10 * MS))


def test_union_merges_overlaps():
    assert trace.union([(5, 7), (1, 3), (2, 4)]) == [(1, 4), (5, 7)]


def test_busy_and_idle_share():
    tr = small_trace()
    assert tr.window_s == pytest.approx(0.010)
    assert trace.busy_s(tr, [0]) == pytest.approx(0.004)
    ctx = type("Ctx", (), {"trace": tr, "chips": [0]})
    assert readers.idle_share(ctx) == pytest.approx(60.0)


def test_op_time_and_durations():
    tr = small_trace()
    kernel, scan = readers.similarity_kernel(M, B, N), readers.sprt_scan(B, N)
    assert trace.op_durations(tr, kernel) == [pytest.approx(0.002)]
    assert trace.op_durations(tr, scan) == [pytest.approx(0.001)]
    # a loop body op nested in its loop counts once
    tr.ops.append(Op(0, "%fusion.4", "jit_scan",
                     "jit_scan %fusion.4 = pred[1,32]{1,0} fusion()",
                     int(6.2 * MS), int(6.8 * MS)))
    assert trace.op_busy_s(tr, scan) == pytest.approx(0.001)
    assert trace.top_ops(tr, 2) == [["jit_estimate/%similarity.1",
                                     pytest.approx(0.002)],
                                    ["jit_estimate/%fusion.2",
                                     pytest.approx(0.002)]]


def test_ops_are_found_by_their_shapes_in_any_program():
    """The kernel and the scan are found in a program of another name; a
    custom call or a loop of other shapes is not taken for them."""
    other_kernel = KERNEL.replace("f32[8,16]{1,0} custom-call(f32[8,32]",
                                  "f32[8,8]{1,0} custom-call(f32[8,32]")
    other_scan = SCAN.replace("pred[16,32]", "pred[16,4]")
    tr = Trace(ops=[Op(0, "%a", "jit_step", "jit_step " + KERNEL, 0, MS),
                    Op(0, "%b", "jit_step", "jit_step " + other_kernel,
                       MS, 2 * MS),
                    Op(0, "%c", "jit_step", "jit_step " + SCAN, 2 * MS, 4 * MS),
                    Op(0, "%d", "jit_step", "jit_step " + other_scan,
                       4 * MS, 8 * MS)],
               window=(0, 10 * MS))
    assert trace.op_durations(tr, readers.similarity_kernel(M, B, N)) == [
        pytest.approx(0.001)]
    assert trace.op_busy_s(tr, readers.sprt_scan(B, N)) == pytest.approx(0.002)


def test_readers_take_host_counts_from_the_untraced_window():
    """mfu divides by the untraced window's seconds; the scan's time is
    shared over the traced window's batches."""
    import importlib.util

    peaks = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}
    ctx = type("Ctx", (), {
        "trace": small_trace(), "chips": [0], "peaks": peaks,
        "layer": {"m": M, "b": B, "n": N, "calls": 100, "elapsed_s": 2.0},
        "traced": {"m": M, "b": B, "n": N, "calls": 4, "elapsed_s": 0.01}})
    # 100 calls of 2mbn + 2m^2 b + 2bmn = 18432 flops in 2 s, of 1e12 flop/s
    assert readers.mfu(ctx, 18432.0) == pytest.approx(100 * 18432 * 100 / 2.0
                                                      / 1e12)
    path = tinyroot.BENCH / "metrics" / "sprt_ms.surveil.py"
    spec = importlib.util.spec_from_file_location("sprt_ms_surveil", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.read(ctx) == pytest.approx(1.0 / 4)


def test_gaps_go_to_the_innermost_span():
    idle = trace.idle_by_span(small_trace(), 0)
    # gaps: 0-1 (h2d), 4-5 (estimate), 5-6 and 7-9 (sprt), 9-10 (none)
    assert idle == {"bench.h2d": pytest.approx(0.001),
                    "bench.estimate": pytest.approx(0.001),
                    "bench.sprt": pytest.approx(0.003),
                    "(between spans)": pytest.approx(0.001)}


def test_load_reads_ops_and_spans_of_a_recorded_trace(tmp_path):
    """A trace recorded here on the CPU: its XLA ops stand in for a device
    plane's, and the harness spans and window come back on the same clock."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("bench.step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))[0]
    from jax.profiler import ProfileData
    op_line = next(line.name for plane in ProfileData.from_file(path).planes
                   for line in plane.lines
                   if line.name.startswith("tf_XLAPjRtCpuClient"))
    tr = trace.load(path, device_plane=r"^/host:CPU$", op_line=op_line)
    assert [n for n, _, _ in tr.spans if n == "bench.step"]
    assert tr.window[1] > tr.window[0]
    assert any("dot" in op.text for op in tr.ops)
    assert 0 < trace.busy_s(tr, [0]) <= tr.window_s


def test_ops_take_the_module_that_holds_their_start():
    modules = [(0, 10, "jit_estimate"), (20, 30, "jit_scan")]
    starts = [0, 20]
    assert trace._module_of(modules, starts, 5) == "jit_estimate"
    assert trace._module_of(modules, starts, 20) == "jit_scan"
    assert trace._module_of(modules, starts, 15) == ""
