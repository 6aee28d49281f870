"""The Anomaly Transformer cell: its work counts against hand counts, its
readers on a hand-made trace, its files through ``bench/spec.py``, and
whole tiny runs on the CPU in which a planted fault makes ``correct``
false."""
import dataclasses
import math

import bench_cells  # noqa: F401  (puts the checkout on sys.path)
import jax
import jax.numpy as jnp
import pytest

from bench import counts_at, device, run, spec, trace
from bench.trace import Event
from repro import telemetry
from repro.models import anomaly_transformer as at

CELL = "train-at-smd-n200"
CFG = spec.cell(CELL)["config"]
TPU0, HOST = "/device:TPU:0", "/host:CPU"
TINY = dict(n_sensors=8, n_fog=2, rounds=2, local_epochs=1, batch_size=4, train_len=40,
            val_len=20, test_len=40, feature_dim=5, win_size=10, d_model=32, n_heads=2,
            e_layers=2, d_ff=32, client_chunk=3)


def test_params_at_the_published_widths():
    # conv 3*38*512; per layer 4 x (512*512 + 512) + (512*8 + 8) + 2 LayerNorms
    # + (512*512 + 512) x 2; final LayerNorm; projection 512*38 + 38
    layer = 4 * 262_656 + 4_104 + 2_048 + 2 * 262_656
    assert counts_at.n_params(CFG) == 58_368 + 3 * layer + 1_024 + 19_494 == 4_825_150


def test_forward_flops_per_window():
    # per layer: Q/K/V/O 4 x 100*512*512, sigma 100*512*8, QK^T and SV
    # 2 x 100*100*512, FFN 2 x 100*512*512; conv 100*3*38*512; out 100*512*38
    layer = 4 * 26_214_400 + 409_600 + 2 * 5_120_000 + 2 * 26_214_400
    macs = 5_836_800 + 3 * layer + 1_945_600
    assert counts_at.forward_flops_per_window(CFG) == 2 * macs == 1_023_180_800


def test_windows_and_wire_slots():
    # 157 stride-1 windows of 100 in 256 rows: 4 batches of 32, one epoch
    assert counts_at.windows_per_sensor_round(CFG) == 128
    assert counts_at.train_flops_per_sensor_round(CFG) == 3 * 1_023_180_800 * 128
    # 590 blocks; 241,258 kept in all, the 62-coordinate tail keeps its 62
    assert counts_at.wire_slots(CFG) == (590, 410)


def test_the_battery_affords_every_round():
    """Each sensor can pay for every round of the published schedule (20
    rounds of 5 epochs), so every round of the cell has participants: the
    compute of the windows it trains at 1 nJ an operation, plus the
    costliest feasible uplink (source level at its 140 dB cap)."""
    from bench.reference import hfl as phys

    pub = CFG["published"]
    epochs = dict(CFG, local_epochs=pub["local_epochs"])
    compute = phys.EPS_OP_J * counts_at.train_flops_per_sensor_round(epochs)
    d = counts_at.n_params(CFG)
    bits = phys.payload_bits(d, round(CFG["rho_s"] * d), CFG["quant_bits"])
    p_ac = 4 * math.pi * phys.P_REF**2 / (phys.RHO_WATER * phys.SOUND_SPEED) * 1e14
    rate = phys.BANDWIDTH_HZ * math.log2(1 + 10 ** (phys.GAMMA_TGT_DB / 10))
    uplink = (p_ac / phys.ETA_EA + phys.P_CIRCUIT_TX) * bits / rate
    assert compute == pytest.approx(1964.5, abs=0.1) and 0 < uplink < 50
    assert pub["rounds"] * (compute + uplink) < CFG["e_init_j"]
    # Table II's 500 J would end every sensor after its first round here
    assert 2 * phys.EPS_OP_J * counts_at.train_flops_per_sensor_round(CFG) > pub["e_init_j"]


def test_the_cell_loads_through_spec():
    cell = spec.cell(CELL)
    assert cell["chips"] == 1 and cell["traffic"]["kind"] == "train_at"
    assert cell["config"]["client_chunk"] == 8 and cell["config"]["d_model"] == 512
    assert spec.driver("train_at").Driver
    names = {m["name"] for m in cell["per_layer"]}
    assert names == {"train_at.mfu", "local_train_us.train_at", "wire_roofline.train_at",
                     "device_idle_share.train_at"}
    assert [m["name"] for m in cell["end_to_end"]] == ["train_sensor_rounds_per_s", "setup_s"]
    assert set(cell["limits"]) == {"loss_rel", "assdis_rel", "change_gap", "f1_abs",
                                   "physics_rel"}


def _ev(plane, name, start, dur, call=False):
    line = trace.OP_LINE if plane != HOST else "python"
    return Event(plane, line, name, float(start), float(dur), call)


def _context(counters):
    evs = [_ev(HOST, "bench.window", 0, 10e9),
           _ev(TPU0, "fusion.1", 0, 8e9),
           _ev(TPU0, "closed_call.7", 8e9, 0.5e9, call=True),
           _ev(TPU0, "closed_call.9", 8.5e9, 0.3e9, call=True)]
    kernels = {"closed_call.7": "compress_wire_blocks", "closed_call.9": "wire_aggregate_blocks"}
    return run.Context(evs, trace.window(evs), kernels, counters, spec.cell(CELL),
                       device.peaks("TPU v5 lite"), 1)


COUNTERS = {"jobs": 2, "sensor_rounds": 1200, "window_s": 10.0}


@pytest.fixture
def recording(monkeypatch):
    telemetry.clear()
    monkeypatch.setattr(telemetry, "recording", lambda: True)
    yield
    telemetry.clear()


def test_readers_on_a_hand_made_trace(recording):
    telemetry.observe("engine.local_windows", [128, 128])
    ctx = _context(COUNTERS)
    # 8.8 s busy of 10
    assert spec.reader("device_idle_share.train_at")(ctx) == pytest.approx(12.0)
    # 8 s outside the wire kernels over 1,200 sensor-rounds
    assert spec.reader("local_train_us.train_at")(ctx) == pytest.approx(8e6 / 1200)
    wire = counts_at.wire_bytes(CFG, 1200, 6) / 819e9 / 0.8 * 100
    assert spec.reader("wire_roofline.train_at")(ctx) == pytest.approx(wire)
    mfu = 1200 * 3 * 1_023_180_800 * 128 / (10.0 * 197e12) * 100
    assert spec.reader("train_at.mfu")(ctx) == pytest.approx(mfu)
    for name in ("wire_roofline.train_at", "train_at.mfu"):
        assert 0.0 < spec.reader(name)(ctx) < 100.0


def test_mfu_reader_fails_when_the_program_counts_other_windows(recording):
    telemetry.observe("engine.local_windows", [157])
    with pytest.raises(ValueError, match="windows"):
        spec.reader("train_at.mfu")(_context(COUNTERS))


def test_device_readers_read_nothing_without_device_events():
    ctx = _context(COUNTERS)
    ctx.events = [e for e in ctx.events if e.plane == HOST]
    for name in ("device_idle_share.train_at", "local_train_us.train_at"):
        assert spec.reader(name)(ctx) is None


def _run_tiny(seed=2**31 + 17):
    from unittest import mock

    cell = spec.cell(CELL)
    cell = dict(cell, config=dict(cell["config"], **TINY))
    with mock.patch("repro.launch.compile_cache.enable", lambda: None):
        return run.run(cell, seed, 0.2, False, jax.devices()[:1], log=lambda msg: None)


def no_prior(monkeypatch):
    """Every prior association is a uniform row."""
    attend = at._anomaly_attention

    def uniform(lp, x, n_heads):
        out, series, prior = attend(lp, x, n_heads)
        return out, series, jnp.full_like(prior, 1.0 / prior.shape[-1])

    monkeypatch.setattr(at, "_anomaly_attention", uniform)


def lambda_sign(monkeypatch):
    """The minimax signs are swapped: S pulled to the prior, P pushed off."""
    loss = at.loss

    def swapped(params, x, cfg=at.ATConfig()):
        return loss(params, x, dataclasses.replace(cfg, k=-cfg.k))

    monkeypatch.setattr(at, "loss", swapped)


def ef_lost(monkeypatch):
    """The error-feedback state is lost between rounds: what the sparse
    wire left out of round 1 never reaches a later round."""
    from repro.core import aggregation as agg

    scan = agg.client_chunk_scan

    def lost(*args, **kwargs):
        fog_sum, fog_weight, err, outputs = scan(*args, **kwargs)
        return fog_sum, fog_weight, jnp.zeros_like(err), outputs

    monkeypatch.setattr(agg, "client_chunk_scan", lost)


@pytest.mark.parametrize("fault", [no_prior, lambda_sign, ef_lost])
def test_a_planted_fault_makes_the_run_incorrect(monkeypatch, fault):
    fault(monkeypatch)
    result = _run_tiny()
    assert result["correct"] is False, result["checks"]


def test_a_sound_tiny_run_is_correct():
    result = _run_tiny()
    assert result["correct"] is True, result["checks"]
    assert set(result["metrics"]) == {"train_sensor_rounds_per_s", "setup_s"}

