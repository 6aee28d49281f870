"""The benchmark's operation and byte counts against hand counts at the
paper autoencoder's sizes (32-16-8-16-32, d = 1,352)."""
import bench_cells  # noqa: F401  (puts the checkout on sys.path)
import pytest

from bench import counts, spec

PAPER = spec.cell("train-paper-n200")["config"]
FLEET = spec.cell("train-fleet-n50k")["config"]


def test_params_of_the_paper_autoencoder():
    # 32*16+16 + 16*8+8 + 8*16+16 + 16*32+32
    assert counts.n_params(PAPER) == 528 + 136 + 144 + 544 == 1352


def test_forward_flops_per_row():
    assert counts.forward_flops_per_row(PAPER) == 2 * (512 + 128 + 128 + 512) == 2560
    assert counts.score_flops_per_row(PAPER) == 2560 + 96


def test_train_flops_per_sensor_round():
    # forward + backward = 3 x forward, 256 samples x 5 epochs
    assert counts.train_flops_per_sensor_round(PAPER) == 3 * 2560 * 256 * 5 == 9_830_400


def test_train_bytes_per_sensor_round():
    # window 256 x 32 f32 read once, params read and update written
    assert counts.train_bytes_per_sensor_round(PAPER) == 4 * (8192 + 2 * 1352) == 43_584


@pytest.mark.parametrize("cfg,fogs", [(PAPER, 20), (FLEET, 2500)])
def test_aggregate_bytes(cfg, fogs):
    # 10 sensor-rounds over 2 rounds: update + EF read, EF written; fog buffers once a round
    assert counts.aggregate_bytes(cfg, 10, 2) == 4 * (3 * 1352 * 10 + fogs * 1352 * 2)


def test_score_bytes():
    # 1,024 rows of 32 f32 in, f32 error and a one-byte flag out; weights once per call
    assert counts.score_bytes(PAPER, 1024, 1) == 1024 * (128 + 4 + 1) + 4 * 1352


def test_roofline_takes_the_binding_bound():
    from bench import trace

    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    # 50 flops -> 0.5 s at peak, 20 bytes -> 2 s: bandwidth binds; measured 4 s
    assert trace.roofline_percent(50.0, 20.0, 4.0, peaks, 1) == pytest.approx(50.0)
    assert trace.roofline_percent(50.0, 20.0, 4.0, peaks, 2) == pytest.approx(25.0)
    assert trace.roofline_percent(50.0, 20.0, 0.0, peaks, 1) is None
