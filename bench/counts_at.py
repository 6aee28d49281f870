"""Operations and bytes of the Anomaly Transformer cell, from the
published, unpadded shapes in its configuration file.

Like ``bench/counts.py``, the work never depends on how the program pads,
blocks or fuses it; times come from the trace or the host clock.
"""
from __future__ import annotations

F32 = 4
BLOCK = 8192          # coordinates of one compression block of the wire
INDEX_BYTES, CODE_BYTES = 4, 1


def n_params(cfg: dict) -> int:
    """Embedding conv (no bias); per layer Q, K, V, O with biases, the
    sigma projection, two LayerNorms and the feed-forward pair; the final
    LayerNorm and the output projection."""
    dim, dm, h, ff = cfg["feature_dim"], cfg["d_model"], cfg["n_heads"], cfg["d_ff"]
    layer = 4 * (dm * dm + dm) + (dm * h + h) + 2 * 2 * dm + (dm * ff + ff) + (ff * dm + dm)
    return 3 * dim * dm + cfg["e_layers"] * layer + 2 * dm + dm * dim + dim


def forward_flops_per_window(cfg: dict) -> int:
    """Multiply-adds of one window's forward pass, two operations each."""
    n, dim, dm, h, ff = (cfg["win_size"], cfg["feature_dim"], cfg["d_model"],
                         cfg["n_heads"], cfg["d_ff"])
    qkvo = 4 * n * dm * dm
    sigma = n * dm * h
    scores_and_mix = 2 * n * n * dm      # QK^T and SV over all heads
    ffn = 2 * n * dm * ff
    embed = n * 3 * dim * dm
    out = n * dm * dim
    return 2 * (embed + cfg["e_layers"] * (qkvo + sigma + scores_and_mix + ffn) + out)


def windows_per_sensor_round(cfg: dict) -> int:
    """Windows one sensor trains on in a round: each epoch shuffles the
    stride-1 window starts and keeps whole minibatches."""
    starts = cfg["train_len"] - cfg["win_size"] + 1
    return cfg["local_epochs"] * (starts // cfg["batch_size"]) * cfg["batch_size"]


def train_flops_per_sensor_round(cfg: dict) -> int:
    """Forward and backward, three times the forward, of every window
    trained."""
    return 3 * forward_flops_per_window(cfg) * windows_per_sensor_round(cfg)


def wire_slots(cfg: dict) -> tuple[int, int]:
    """(blocks, kept coordinates per block) of one update on the wire: a
    uniform count per block of 8,192 that keeps about rho_s d in all."""
    d = n_params(cfg)
    nb = -(-d // BLOCK)
    tail = d - (nb - 1) * BLOCK
    target = max(1, round(cfg["rho_s"] * d))
    k = target / nb
    if nb > 1 and k > tail:
        k = (target - tail) / (nb - 1)
    return nb, min(BLOCK, max(1, round(k)))


def wire_bytes(cfg: dict, sensor_rounds: int, rounds: int) -> int:
    """Bytes the two wire kernels must move: per sensor and round read the
    update and the error-feedback buffer, write the buffer back, write the
    wire (an int32 index and an int8 code per slot, an f32 scale per
    block) and read it again; per round write every fog's buffer once."""
    d = n_params(cfg)
    nb, k = wire_slots(cfg)
    wire = nb * (k * (INDEX_BYTES + CODE_BYTES) + F32)
    return (F32 * 3 * d + 2 * wire) * sensor_rounds + F32 * cfg["n_fog"] * d * rounds
