"""Operations and bytes of the algorithm, from its unpadded shapes.

Every roofline and utilisation metric divides work counted here by time
read from the trace, so the work never depends on how a kernel pads,
blocks or fuses it.  Sizes come from the configuration file.
"""
from __future__ import annotations

F32 = 4


def ae_dims(cfg: dict) -> tuple[int, ...]:
    d = cfg["feature_dim"]
    return (d, *cfg["hidden"], d)


def n_params(cfg: dict) -> int:
    dims = ae_dims(cfg)
    return sum(a * b + b for a, b in zip(dims[:-1], dims[1:]))


def forward_flops_per_row(cfg: dict) -> int:
    """Multiply-adds of the autoencoder's layers, two operations each."""
    dims = ae_dims(cfg)
    return 2 * sum(a * b for a, b in zip(dims[:-1], dims[1:]))


def score_flops_per_row(cfg: dict) -> int:
    """Forward pass plus the squared error against the input (subtract,
    square, add for each feature)."""
    return forward_flops_per_row(cfg) + 3 * cfg["feature_dim"]


def train_flops_per_sensor_round(cfg: dict) -> int:
    """Local training of one sensor in one round: forward and backward
    (three times the forward) over every sample of every local epoch."""
    return 3 * forward_flops_per_row(cfg) * cfg["train_len"] * cfg["local_epochs"]


def train_bytes_per_sensor_round(cfg: dict) -> int:
    """Local training reads the sensor's window once and the broadcast
    parameters once, and writes its update."""
    return F32 * (cfg["train_len"] * cfg["feature_dim"] + 2 * n_params(cfg))


def aggregate_bytes(cfg: dict, sensor_rounds: int, rounds: int) -> int:
    """Compression and fog aggregation: per sensor and round read the
    update and the error-feedback buffer and write the buffer back; per
    round write every fog's buffer once."""
    d = n_params(cfg)
    return F32 * (3 * d * sensor_rounds + cfg["n_fog"] * d * rounds)


def score_bytes(cfg: dict, rows: int, calls: int) -> int:
    """Scoring reads each row and writes its error (f32) and flag (one
    byte); each call reads the weights once."""
    return rows * (F32 * cfg["feature_dim"] + F32 + 1) + calls * F32 * n_params(cfg)
