"""The reduction from trace events to busy time, idle share, kernel time
and the breakdown: on hand-made events and on a small recorded trace."""
import json
import os

import bench_cells
import pytest

from bench import trace
from bench.trace import Event

TPU0, TPU1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"


def ev(plane, name, start, dur, line=None):
    return Event(plane, line or (trace.OP_LINE if plane != HOST else "python"), name,
                 float(start), float(dur))


HAND = [
    ev(HOST, "bench.window", 0, 1000),
    ev(HOST, "bench.job", 0, 600),
    ev(HOST, "bench.step", 650, 300),
    ev(TPU0, "local_train_blocks.3", 100, 200),
    ev(TPU0, "fusion.12", 250, 100),            # overlaps the kernel: counted once
    ev(TPU0, "compress_aggregate_blocks", 500, 50),
    ev(TPU0, "local_train_blocks.3", 900, 200),  # runs past the window's end
    ev(TPU1, "local_train_blocks.1", 0, 500),
]


def test_union_merges_and_clips():
    assert trace.union([(5, 9), (0, 3), (2, 4), (20, 30)], 1, 25) == [(1, 4), (5, 9), (20, 25)]
    assert trace.union([], 0, 10) == []


def test_busy_and_idle_average_over_chips():
    win = trace.window(HAND)
    assert win == (0.0, 1000.0)
    # TPU0: [100, 350) + [500, 550) + [900, 1000) = 400; TPU1: 500
    assert trace.busy_ns(HAND, win) == pytest.approx(450.0)
    assert trace.idle_percent(HAND, win) == pytest.approx(55.0)


def test_kernel_time_by_stable_name():
    win = (0.0, 1000.0)
    # TPU0: 200 + 100 (clipped); TPU1: 500; averaged over 2 chips
    assert trace.kernel_ns(HAND, win, {"local_train_blocks"}, {}) == pytest.approx(400.0)
    assert trace.kernel_ns(HAND, win, {"compress_aggregate_blocks"}, {}) == pytest.approx(25.0)
    assert trace.kernel_ns(HAND, win, {"score_blocks"}, {}) == 0.0
    assert trace.kernel_ns(HAND, win, {"local_train_blocks_v2"}, {}) == 0.0


HLO = """
  %closed_call.10 = (f32[20,1,64,128]{3,2,1,0}, f32[200,1,64,128]{3,2,1,0}) custom-call(s32[200]{0} %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(trial)/vmap(vmap())/while/body/closed_call/jit(_compress_aggregate_pallas)/jit(compress_aggregate_blocks)/while/body/closed_call/pallas_call" source_file="x.py"}
  %local_train_blocks.16 = (f32[3,200,128,128]{3,2,1,0}) custom-call(f32[3,200,256,128]{3,2,1,0} %p), custom_call_target="tpu_custom_call", metadata={op_name="jit(trial)/jit(_local_train_pallas)/jit(local_train_blocks)/pallas_call"}
  %fusion.3 = f32[20]{0} fusion(f32[20]{0} %a), kind=kLoop, calls=%fused
"""


def test_a_kernel_is_named_by_its_wrapper_in_the_op_name():
    kernels = trace.kernel_names([HLO])
    assert kernels == {"closed_call.10": "compress_aggregate_blocks",
                       "local_train_blocks.16": "local_train_blocks"}
    call = Event(TPU0, trace.OP_LINE, "closed_call.10", 0.0, 5.0, True)
    assert trace.label(call, kernels) == "compress_aggregate_blocks"
    assert trace.label(call._replace(call=False), kernels) == "closed_call"
    assert trace.instruction("%closed_call.10 = (f32[20,1,64,128]) custom-call(s32[200])") \
        == "closed_call.10"
    loop = Event(TPU0, trace.OP_LINE, "while.94", 0.0, 10.0)
    assert trace.leaf_ops([loop, call]) == [call]
    assert trace.kernel_ns([loop, call], (0.0, 10.0), {"compress_aggregate_blocks"},
                           kernels) == 5.0


def test_breakdown():
    win = (0.0, 1000.0)
    top = trace.top_ops(HAND, win, {})
    assert top[0][0] == "local_train_blocks" and top[0][1] == pytest.approx(400e-9)
    gaps = trace.idle_gaps(HAND, win)
    # TPU0 idle: [0, 100) in bench.job, [350, 500) in bench.job, [550, 900) in bench.step
    assert gaps[0] == ["bench.step", pytest.approx(350e-9)]
    assert sorted(g[1] for g in gaps) == pytest.approx([100e-9, 150e-9, 350e-9])


def test_no_device_events_read_nothing():
    host_only = [e for e in HAND if e.plane == HOST]
    assert trace.idle_percent(host_only, (0.0, 1000.0)) is None
    assert trace.idle_gaps(host_only, (0.0, 1000.0)) == []


RECORDED = os.path.join(bench_cells.ROOT, "bench", "testdata", "train_trace.json")


def test_recorded_chip_trace():
    """70 ms of a train-paper-n200 job on one TPU v5e, with the program's
    custom-call names: both kernels are found and fit inside busy time."""
    with open(RECORDED) as f:
        rec = json.load(f)
    evs, kernels = [Event(*e) for e in rec["events"]], rec["kernels"]
    win = trace.window(evs)
    assert win is not None and trace.device_planes(evs) == [TPU0]
    busy = trace.busy_ns(evs, win)
    assert 0.9 * (win[1] - win[0]) < busy <= win[1] - win[0]
    lt = trace.kernel_ns(evs, win, {"local_train_blocks"}, kernels)
    agg = trace.kernel_ns(evs, win, {"compress_aggregate_blocks"}, kernels)
    assert 0.0 < agg < lt < busy and lt + agg <= busy
    top = [name for name, _ in trace.top_ops(evs, win, kernels)]
    assert top[0] == "local_train_blocks" and "compress_aggregate_blocks" in top


def _context(evs, kernels, counters, cell_name):
    from bench import device, run, spec

    cell = spec.cell(cell_name)
    return run.Context(evs, trace.window(evs), kernels, counters, cell,
                       device.peaks("TPU v5 lite"), 1)


def test_training_readers_on_the_recorded_trace():
    """Every per-layer reader of a training cell reads a number from the
    recorded slice (about 2 job-rounds of 3 seeds x 200 sensors), and no
    share of a roofline or a peak passes 100%."""
    from bench import spec

    with open(RECORDED) as f:
        rec = json.load(f)
    evs = [Event(*e) for e in rec["events"]]
    win = trace.window(evs)
    counters = {"jobs": 0.1, "sensor_rounds": 1200, "window_s": (win[1] - win[0]) / 1e9}
    ctx = _context(evs, rec["kernels"], counters, "train-paper-n200")
    for m in spec.cell("train-paper-n200")["per_layer"]:
        value = spec.reader(m["name"])(ctx)
        assert value is not None and value >= 0.0, m["name"]
        if m["unit"] == "%":
            assert value <= 100.0, (m["name"], value)


def test_readers_read_nothing_without_device_events():
    from bench import spec

    host = [Event(HOST, "python", "bench.window", 0.0, 1e9),
            Event(HOST, "python", "bench.step", 0.0, 1e6)]
    counters = {"rows_in_window": 0, "rows_scored_in_window": 0, "steps_in_window": 0,
                "window_s": 1.0}
    for name in ("serve-paper-latency", "serve-paper-bulk"):
        ctx = _context(host, {}, counters, name)
        for m in spec.cell(name)["per_layer"]:
            if m["source"] == "device_trace":
                assert spec.reader(m["name"])(ctx) is None, m["name"]
