"""Serving traffic: telemetry requests scored by one ``ScoringService``.

Set-up makes the telemetry and the autoencoder's weights on the device
from the seed, takes the fixed threshold tau as the configured percentile
of the plain reference's errors on the validation rows, builds the
service over an in-memory store that holds those weights, and scores one
request per bucket the traffic uses, which compiles (or loads) each
bucket's program.

Two loops, both in this one process and on the wall clock:

* ``open``: requests arrive as a Poisson stream at the traffic's fixed
  rate.  The loop submits every request that is due, runs a micro-batch
  whenever the service's flush policy asks for one, and otherwise sleeps
  until the next arrival or deadline.  Each request is timed from when
  it was due to the end of the micro-batch that completed it; requests
  due in the window are followed to completion after it closes.
* ``closed``: ``outstanding`` requests are always in flight; each
  micro-batch's completed requests are replaced at once.  The rate is the
  rows of the requests completed in the window over the window.

The service scores its queue first in, first out, so the requests a
micro-batch completed follow from the rows it reports.  ``correct``
compares every answer of the run, error and flag, with the plain
reference's forward pass at ``highest`` matmul precision.
"""
from __future__ import annotations

import functools
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import arrivals, check, data
from bench.reference import hfl as ref

WAIT_PAST_CLOSE_S = 60.0


class _Store:
    """A store that has published one round: the seeded weights."""

    def __init__(self, params):
        self.params = params

    def latest_step(self):
        return 1

    def restore(self, like, step=None):
        return self.params, 1


class Driver:
    def __init__(self, cell: dict, seed: int, span):
        self.cfg, self.traffic, self.span = cell["config"], cell["traffic"], span
        self.serving = self.cfg["serving"]
        self.rng = np.random.default_rng([seed, 1])
        self.key = int(np.random.default_rng([seed, 0]).integers(2**31 - 1))
        self.due: list[float] = []        # due time of each request, window clock
        self.done_at: list[float] = []    # completion time, window clock
        self.late: list[float] = []       # how late each was submitted
        # Each request's sensor, first row and row count, kept as lists of
        # ints so that the bookkeeping adds no objects for the collector.
        self.sensor: list[int] = []
        self.start: list[int] = []
        self.nrows: list[int] = []
        self.rids: list[int | None] = []
        self.gc_pauses: list[float] = []

    # -- set-up ---------------------------------------------------------

    def setup(self) -> None:
        from repro.serving.service import ScoringService

        cfg = self.cfg
        kd, kw = jax.random.split(jax.random.key(self.key))
        with self.span("bench.data"):
            ds = data.dataset(kd, cfg)
            self.params = data.ae_weights(kw, ref.ae_dims(cfg))
            self.test = np.asarray(ds["test"])
            val = ds["val"].reshape(-1, cfg["feature_dim"])
            self.tau = float(np.percentile(reference_errors(self.params, val),
                                           self.serving["tau_percentile"]))
        self.svc = ScoringService(
            _Store(self.params), self.params, buckets=tuple(self.serving["buckets"]),
            tau=self.tau, max_wait_s=self.serving["max_wait_s"],
            weight_dtype=self.serving["weight_dtype"], clock=time.perf_counter)
        with self.span("bench.warm"):
            for rows in self.traffic["warm_rows"]:
                self.svc.submit(self.test.reshape(-1, cfg["feature_dim"])[:rows])
                self.svc.drain()
        self.svc.stats.e2e_latency_s.clear()
        self._rows_scored = 0
        self._cum_end: list[int] = []
        self._next_done = 0

    def _draw(self, n: int) -> None:
        t = self.traffic
        r = arrivals.requests(self.rng, n, self.cfg["n_sensors"], tuple(t["rows"]),
                              self.cfg["test_len"])
        self.sensor += r["sensor"].tolist()
        self.start += r["start"].tolist()
        self.nrows += r["rows"].tolist()

    @property
    def reqs(self) -> list[tuple[int, int, int]]:
        """(sensor, first row, rows) of every request drawn."""
        return list(zip(self.sensor, self.start, self.nrows))

    def _submit(self, i: int) -> None:
        s, start, rows = self.sensor[i], self.start[i], self.nrows[i]
        with self.span("bench.submit"):
            rid = self.svc.submit(self.test[s, start:start + rows])
        self.rids.append(rid)
        total = self._cum_end[-1] if self._cum_end else 0
        self._cum_end.append(total + (rows if rid is not None else 0))
        self.done_at.append(np.nan)

    def _step(self, t0: float) -> int:
        with self.span("bench.step"):
            rows = self.svc.step()
        now = time.perf_counter() - t0
        self._rows_scored += rows
        done = 0
        while (self._next_done < len(self._cum_end)
               and self._cum_end[self._next_done] <= self._rows_scored):
            if self.rids[self._next_done] is not None:
                self.done_at[self._next_done] = now
                done += 1
            self._next_done += 1
        return done

    # -- window ---------------------------------------------------------

    def window(self, seconds: float) -> None:
        gc.callbacks.append(self._gc_timer)
        try:
            if self.traffic["loop"] == "open":
                self._open(seconds)
            else:
                self._closed(seconds)
        finally:
            gc.callbacks.remove(self._gc_timer)

    def _gc_timer(self, phase: str, info: dict) -> None:
        """Times the interpreter's collections in the window, so that a
        stall of the loop can be told apart from one of the host."""
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        else:
            self.gc_pauses.append(time.perf_counter() - self._gc_t0)

    def _open(self, seconds: float) -> None:
        t = self.traffic
        self.due = arrivals.poisson(self.rng, t["rate_hz"], seconds).tolist()
        self._draw(len(self.due))
        n, i = len(self.due), 0
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter() - t0
            while i < n and self.due[i] <= now:
                self._submit(i)
                self.late.append(time.perf_counter() - t0 - self.due[i])
                i += 1
            if self.svc.should_flush():
                self._step(t0)
                continue
            if i == n and self._next_done == n:
                break
            if now > seconds + WAIT_PAST_CLOSE_S:
                break
            wake = [t0 + self.due[i]] if i < n else []
            deadline = self.svc.next_deadline()
            if deadline is not None:
                wake.append(deadline)
            if wake:
                pause = min(wake) - time.perf_counter()
                if pause > 0:
                    time.sleep(pause)
        self.window_s = seconds

    def _closed(self, seconds: float) -> None:
        k = self.traffic["outstanding"]
        self._draw(k)
        t0 = time.perf_counter()
        steps0 = self.svc.stats.steps
        for i in range(k):
            self._submit(i)
            self.due.append(0.0)
        while True:
            done = self._step(t0)
            now = time.perf_counter() - t0
            if now >= seconds:
                break
            start = len(self.nrows)
            self._draw(done)
            for i in range(start, start + done):
                self._submit(i)
                self.due.append(now)
        self.window_s = now
        self.steps_in_window = self.svc.stats.steps - steps0
        self.rows_scored_in_window = self._rows_scored
        self.rows_in_window = sum(self.nrows[i] for i in range(len(self.done_at))
                                  if not np.isnan(self.done_at[i]))
        while self.svc.pending_rows() > 0:
            self._step(t0)

    # -- results --------------------------------------------------------

    def latencies(self) -> np.ndarray:
        done = np.asarray(self.done_at, np.float64)
        due = np.asarray(self.due, np.float64)
        ok = ~np.isnan(done)
        return (done[ok] - due[ok])

    def end_to_end(self) -> dict[str, float]:
        if self.traffic["loop"] == "open":
            lat = self.latencies() * 1e3
            return {f"serve_p{q}_ms": float(np.percentile(lat, q)) for q in (50, 95, 99)}
        return {"serve_rows_per_s": self.rows_in_window / self.window_s}

    def counters(self) -> dict:
        st = self.svc.stats
        late = np.asarray(self.late or [0.0])
        out = {"requests": len(self.nrows), "rows": int(sum(self.nrows)),
               "steps": st.steps, "partial_flushes": st.partial_flushes,
               "window_s": self.window_s,
               "generator_late_p50_ms": float(np.percentile(late, 50) * 1e3),
               "generator_late_p99_ms": float(np.percentile(late, 99) * 1e3),
               "generator_late_last_ms": float(late[-1] * 1e3),
               "generator_late_max_ms": float(np.max(late) * 1e3),
               "gc_collections": len(self.gc_pauses),
               "gc_pause_max_ms": max(self.gc_pauses, default=0.0) * 1e3}
        if self.traffic["loop"] == "closed":
            out.update(rows_in_window=self.rows_in_window,
                       rows_scored_in_window=self.rows_scored_in_window,
                       steps_in_window=self.steps_in_window)
        return out

    def hlo_texts(self) -> list[str]:
        """The compiled score program of every bucket the run used, as text."""
        d = self.cfg["feature_dim"]
        return [self.svc.programs.fn(b).lower(
                    self.svc.params, np.zeros((b, d), np.float32),
                    np.zeros((b,), np.float32)).compile().as_text()
                for b in sorted(self.svc.stats.compiles_by_bucket)]

    def attempted_failed(self) -> tuple[int, int]:
        missing = sum(1 for d, r in zip(self.done_at, self.rids) if r is None or np.isnan(d))
        return len(self.rids), missing

    # -- correct --------------------------------------------------------

    def release(self) -> None:
        self.results = self.svc.drain()
        self.svc = None

    def check_numbers(self) -> dict[str, float]:
        rows, errs, flags, missing = [], [], [], 0
        for (s, start, n), rid in zip(self.reqs, self.rids):
            res = self.results.get(rid) if rid is not None else None
            if res is None or np.shape(res.error) != (n,):
                missing += 1
                continue
            rows.append(self.test[s, start:start + n])
            errs.append(np.asarray(res.error))
            flags.append(np.asarray(res.flag))
        rows = np.concatenate(rows)
        err, flag = np.concatenate(errs), np.concatenate(flags)
        want = reference_errors(self.params, jnp.asarray(rows))
        return score_numbers(err, flag, want, self.tau, missing)


def score_numbers(err, flag, want, tau: float, missing: int) -> dict[str, float]:
    """``score_gap``: the widest gap between a row's error and the
    reference's, as a share of tau, the scale the detector decides on.
    ``flag_flips``: flags that disagree with the reference where the
    error itself lies on the reference's side of tau (a flag that follows
    an error across tau is score_gap's to judge).  ``unanswered``:
    requests with no result or one of the wrong length."""
    same_side = (err > tau) == (want > tau)
    flips = int(np.sum((flag != (want > tau)) & same_side))
    return {"score_gap": float(np.max(np.abs(err - want)) / tau), "flag_flips": float(flips),
            "unanswered": float(missing)}


def reference_errors(params, rows, dtype=jnp.float32, block: int = 1 << 16) -> np.ndarray:
    """The plain reference's reconstruction error of every row, in blocks."""
    layers = [(p["w"], p["b"]) for p in params]
    fn = _errors(dtype)
    out = []
    for i in range(0, rows.shape[0], block):
        with jax.default_matmul_precision("highest"):
            out.append(np.asarray(fn(layers, rows[i:i + block])))
    return np.concatenate(out).astype(np.float64)


@functools.cache
def _errors(dtype):
    def fn(layers, x):
        cast = [(w.astype(dtype), b.astype(dtype)) for w, b in layers]
        return ref.ae_error(cast, x.astype(dtype)).astype(jnp.float32)

    return jax.jit(fn)
