"""One interface for the per-sensor anomaly detectors the federated round
trains and evaluates.

A :class:`Detector` says everything the round loops, the client solver,
the evaluation and the energy model need to know about a model:

* ``init(key, feature_dim)`` -> params;
* ``loss(params, batch)`` -> ``(objective, stats)``: local SGD follows the
  gradient of ``objective``; ``stats`` is a dict of scalars that always
  holds the reported ``"loss"`` and may hold more (each becomes a
  per-round metric of the round loop, averaged over active clients);
* ``score(params, x)`` -> per-point anomaly scores, higher is more
  anomalous: rows ``(n, D) -> (n,)``, or windows ``(n, L, D) -> (n, L)``;
* ``window``: ``None`` when the detector trains and scores rows, else the
  window length L (training batches are stride-1 windows of the client's
  series, evaluation scores non-overlapping windows);
* ``forward_flops(params)``: operations of one sample's forward pass (a
  row, or a window), from the parameters' shapes;
* ``fusable``: local training may run in the fused MLP local-train kernel
  (``kernels/fused_local_train``), which only the paper autoencoder can.

``models/autoencoder.detector`` and ``models/anomaly_transformer.detector``
build the two instances.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax

Params = Any


@dataclasses.dataclass(frozen=True)
class Detector:
    name: str
    init: Callable[[jax.Array, int], Params]
    loss: Callable[[Params, jax.Array], tuple[jax.Array, dict]]
    score: Callable[[Params, jax.Array], jax.Array]
    forward_flops: Callable[[Params], int]
    window: int | None = None
    fusable: bool = False

    def samples(self, n_rows: int) -> int:
        """Training samples in a client series of ``n_rows`` rows: the rows
        themselves, or its stride-1 windows."""
        return n_rows if self.window is None else n_rows - self.window + 1

    def trained_per_round(self, n_rows: int, batch_size: int, epochs: int) -> int:
        """Samples one client trains on in a round: whole minibatches of each
        epoch's shuffle, the remainder dropped."""
        return epochs * (self.samples(n_rows) // batch_size) * batch_size

    def train_flops(self, params: Params, n_rows: int, batch_size: int,
                    epochs: int) -> int:
        """Local training compute of one client (Eq. 21 / Sec. III-D): forward
        and backward, three times the forward.  A row detector is charged
        every row of every epoch, |D_i| E as Eq. 21 writes it; a window
        detector the windows it trains (:meth:`trained_per_round`)."""
        n = (n_rows * epochs if self.window is None
             else self.trained_per_round(n_rows, batch_size, epochs))
        return 3 * self.forward_flops(params) * n


def choose(detector: Detector | None, hidden: tuple[int, ...] | None) -> Detector:
    """The model a caller asked for: ``detector``, else the paper
    autoencoder at ``hidden`` widths (its default widths when None).  The
    two arguments could disagree, so giving both is refused."""
    from repro.models import autoencoder as ae

    if detector is None:
        return ae.detector() if hidden is None else ae.detector(tuple(hidden))
    if hidden is not None:
        raise ValueError("choose the model by `detector` or by the autoencoder's "
                         "`hidden` widths, not both")
    return detector


def as_detector(loss_fn: Detector | Callable) -> Detector:
    """``loss_fn`` itself when it is a :class:`Detector`; a plain loss
    function (the round loops' historical argument) becomes a row
    detector on MLP parameters: the paper autoencoder when it is
    ``autoencoder.loss``, else the same shape trained by the scan path,
    which refuses any other parameter tree when it counts the work."""
    if isinstance(loss_fn, Detector):
        return loss_fn
    from repro.models import autoencoder as ae

    if loss_fn is ae.loss:
        return ae.detector()

    def loss(params, batch):
        value = loss_fn(params, batch)
        return value, {"loss": value}

    def forward_flops(params):
        if not (isinstance(params, (list, tuple))
                and all(isinstance(layer, dict) and "w" in layer for layer in params)):
            raise TypeError(
                "a plain loss function trains MLP parameters (a list of "
                "{'w', 'b'} layers) only; pass a repro.models.detector.Detector "
                "with its own forward_flops for any other model")
        return ae.forward_flops(params)

    return dataclasses.replace(ae.detector(), name="custom", loss=loss,
                               forward_flops=forward_flops, fusable=False)
