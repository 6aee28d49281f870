"""The per-layer readers of the program's own spans and counters, on
records filled by hand."""
import sys

import bench_cells  # noqa: F401  (puts the checkout on sys.path)
import numpy as np
import pytest

from bench import spec
from repro import telemetry

PROGRAM = [m["name"] for m in spec.load_benchmark()["per_layer"]
           if m["source"] in ("program_span", "program_counter")]
SPANS_NS = {"serve.submit": [2_000.0, 4_000.0], "serve.assemble": [1e6, 3e6],
            "serve.transfer": [2e6, 2e6], "serve.complete": [0.5e6, 1.5e6],
            "engine.prepare": [10e6, 20e6], "engine.execute": [600e6, 600e6],
            "engine.publish": [1e6, 3e6]}
WAITS_S = np.arange(1, 101) * 1e-3          # 1 .. 100 ms
EXPECTED = {
    "queue_wait_p95_ms.serve_latency": float(np.percentile(WAITS_S, 95)) * 1e3,
    "submit_us.serve_latency": 3.0, "submit_us.serve_bulk": 3.0,
    "step_assemble_ms.serve_latency": 2.0, "step_assemble_ms.serve_bulk": 2.0,
    "step_transfer_ms.serve_latency": 2.0, "step_transfer_ms.serve_bulk": 2.0,
    "step_complete_ms.serve_latency": 1.0, "step_complete_ms.serve_bulk": 1.0,
    "job_host_ms.train": 17.0,               # (15 + 2) ms a job
}


@pytest.fixture
def recording(monkeypatch):
    """Records as a profiler session would, without one."""
    telemetry.clear()
    monkeypatch.setattr(telemetry, "recording", lambda: True)
    yield
    telemetry.clear()


def _fill():
    for name, values in SPANS_NS.items():
        telemetry.observe(name, values)
    telemetry.observe("serve.queue_wait_s", WAITS_S)


def test_every_program_metric_is_read_here():
    assert sorted(PROGRAM) == sorted(EXPECTED)


@pytest.mark.parametrize("name", PROGRAM)
def test_reader_on_hand_filled_records(recording, name):
    _fill()
    assert spec.reader(name)(None) == pytest.approx(EXPECTED[name], rel=1e-12)


@pytest.mark.parametrize("name", PROGRAM)
def test_reader_reads_nothing_when_nothing_was_recorded(recording, name):
    assert spec.reader(name)(None) is None


@pytest.mark.parametrize("name", PROGRAM)
def test_reader_reads_nothing_once_a_ring_wrapped(recording, name):
    _fill()
    for n in list(SPANS_NS) + ["serve.queue_wait_s"]:
        telemetry.observe(n, np.ones(telemetry.RING))
    assert spec.reader(name)(None) is None


@pytest.mark.parametrize("name", PROGRAM)
def test_reader_reads_nothing_from_a_program_without_telemetry(monkeypatch, name):
    import repro

    monkeypatch.setitem(sys.modules, "repro.telemetry", None)
    monkeypatch.delattr(repro, "telemetry")
    assert spec.reader(name)(None) is None


def test_job_host_time_without_a_publish(recording):
    telemetry.observe("engine.prepare", SPANS_NS["engine.prepare"])
    assert spec.reader("job_host_ms.train")(None) == pytest.approx(15.0)
