"""The reduction from trace to per-layer metrics, on a hand-built trace,
and the operation counts, against values worked out by hand."""
from __future__ import annotations

import importlib.util
from pathlib import Path

import pytest

from chipbench import flops, trace
from chipbench.trace import Event, Trace

ROOT = Path(__file__).resolve().parent.parent
MS = 1_000_000  # ns
# The int8 uplink kernel as the chip's trace names it: 10 clients' rows
# of 1,024 values, the values staged outside HBM (S(1)), the noise in HBM.
QUANT = ('%vmap__.13 = (s8[10,2304,1024]{2,1,0:T(8,128)(4,1)}, '
         'f32[10,2304,1]{2,1,0:T(8,128)S(1)}) custom-call('
         'f32[10,2304,1024]{2,1,0:T(8,128)S(1)} %copy-done.17, '
         'f32[10,2304,1024]{2,1,0:T(8,128)} %pad.491), '
         'custom_call_target="tpu_custom_call", operand_layout_constraints='
         '{f32[10,2304,1024]{2,1,0}, f32[10,2304,1024]{2,1,0}}')


def _trace() -> Trace:
    """Window 0..100 ms. Chip 0 runs ops 10-30, 20-40 (overlapping), and
    a module 50-70 holding ops 50-60 and 60-65; chip 1 runs 0-50."""
    return Trace(
        ops={0: [Event("fusion.1", 10 * MS, 20 * MS),
                 Event("convolution.7", 20 * MS, 20 * MS),
                 Event("while.3", 50 * MS, 20 * MS),
                 Event("all-reduce.2", 50 * MS, 10 * MS),
                 Event(QUANT, 60 * MS, 5 * MS),
                 Event("fusion.9", 120 * MS, 5 * MS)],
             1: [Event("fusion.1", 0, 50 * MS)]},
        modules={0: [Event("jit_chunk_step(12)", 10 * MS, 30 * MS),
                     Event("jit_eval_acc(3)", 50 * MS, 20 * MS)],
                 1: [Event("jit_chunk_step(12)", 0, 50 * MS)]},
        host=[Event(trace.WINDOW, 0, 100 * MS),
              Event(trace.CALL, 0, 45 * MS),
              Event("PjitFunction(eval_acc)", 42 * MS, 8 * MS),
              Event(trace.CALL, 45 * MS, 55 * MS)])


def test_busy_union_of_overlapping_ops():
    tr = _trace()
    # chip 0: [10, 40] + [50, 70] = 50 ms (fusion.9 lies past the window)
    assert trace.busy_ns(tr, 0) == 50 * MS
    assert trace.busy_ns(tr, 1) == 50 * MS
    assert trace.busy_s(tr) == pytest.approx(0.050)


def test_idle_share_and_window():
    tr = _trace()
    assert tr.window_s == pytest.approx(0.1)
    assert trace.idle_share(tr) == pytest.approx(0.5)
    assert trace.idle_share(Trace(host=tr.host)) is None


def test_module_time_grouped_by_name():
    tr = _trace()
    assert trace.module_s(tr, "chunk_step") == pytest.approx(0.040)
    assert trace.module_s(tr, "chunk_step", chip=0) == pytest.approx(0.030)
    assert trace.module_s(tr, "eval_acc") == pytest.approx(0.010)


def test_self_time_subtracts_nested_ops():
    tr = _trace()
    own = {e.name: t for e, t in trace.self_times(tr.ops[0])}
    assert own["while.3"] == 5 * MS  # 20 minus 10 and 5 nested
    assert trace.op_s(tr, lambda n: "custom_call" in n, 0) == pytest.approx(
        0.005)
    assert trace.collective_s(tr) == {0: pytest.approx(0.010), 1: 0.0}
    kinds = dict(trace.top_ops(tr))
    # fusion.1 runs 20 ms on chip 0 and 50 on chip 1: 35 ms a chip.
    assert kinds["fusion"] == pytest.approx(0.035)


def test_idle_gaps_labelled_by_innermost_host_event():
    tr = _trace()
    assert trace.gaps(tr, 0) == [(0, 10 * MS), (40 * MS, 50 * MS),
                                 (70 * MS, 100 * MS)]
    gaps = dict(trace.idle_gaps(tr))
    # 0-10 in the first call; 40-50's middle (45) in the eval dispatch;
    # 70-100 in the second call.
    assert gaps == {trace.CALL: pytest.approx(0.040),
                    "PjitFunction(eval_acc)": pytest.approx(0.010)}


def test_flops_from_the_published_macs():
    mnist = {"input_hw": [28, 28], "in_channels": 1, "n_classes": 10,
             "conv_channels": [32, 64], "kernel": 5, "fc_dim": 512}
    cifar = dict(mnist, input_hw=[32, 32], in_channels=3)
    assert flops.forward_macs(mnist) == 12_273_152
    assert flops.forward_macs(cifar) == 17_667_072
    assert flops.train_flops_per_sample(mnist) == 6 * 12_273_152
    assert flops.eval_flops_per_sample(cifar) == 2 * 17_667_072
    # cifar's update is 2110 rows of 1024 values, padded to 2304. In HBM:
    # the int8 output and the float32 noise; the values and the scales
    # are staged in memory space 1.
    assert flops.hbm_bytes(QUANT) == 10 * 2304 * 1024 * (1 + 4)


def test_unknown_device_kind_is_refused():
    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        flops.peaks("TPU v9 imaginary")


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"m_{name}", ROOT / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class _Ctx:
    trace = _trace()
    window_s = 0.1
    chips = 2
    rounds = 10
    evals = 1
    train_samples = 1000
    eval_samples = 500
    train_flops_per_sample = 6 * 12_273_152
    eval_flops_per_sample = 2 * 12_273_152
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    retraces = 0


def test_metric_readers_by_hand():
    ctx = _Ctx()
    flop = 1000 * 6 * 12_273_152 + 500 * 2 * 12_273_152
    assert _reader("mfu")(ctx) == pytest.approx(
        100 * flop / (0.1 * 2 * 197e12))
    assert _reader("idle_share")(ctx) == pytest.approx(50.0)
    assert _reader("chunk_device_ms_per_round")(ctx) == pytest.approx(4.0)
    assert _reader("eval_device_ms")(ctx) == pytest.approx(10.0)
    assert _reader("retraces")(ctx) == 0
    assert _reader("collective_ms_per_round")(ctx) == pytest.approx(1.0)
    nbytes = 10 * 2304 * 1024 * 5
    assert _reader("quantize_roofline")(ctx) == pytest.approx(
        100 * nbytes / 819e9 / 0.005)


def test_readers_return_nothing_without_their_events():
    ctx = _Ctx()
    ctx.trace = Trace(host=_trace().host)
    assert _reader("quantize_roofline")(ctx) is None
    assert _reader("collective_ms_per_round")(ctx) is None
    assert _reader("chunk_device_ms_per_round")(ctx) is None
    assert _reader("idle_share")(ctx) is None
