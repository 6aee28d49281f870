"""Smoke test: the federated trainer and the scoring service on a TPU, with
every main-path Pallas kernel compiled.

    python chip_smoke.py              # one chip: train, train-chunked,
                                      # robust, sweep, serve (f32 + int8)
    python chip_smoke.py --chips 4    # only the client-sharded Engine cell
                                      # over 4 chips, against one device

Every phase drives the system through its user entry points
(``Engine.run`` / ``Engine.sweep``, ``CheckpointStore``,
``ScoringService``) at the paper's deployment, with data and model weights
drawn from a fixed seed.  It checks that the engine resolved the compiled
Pallas backend (never interpret mode), that each kernel the phase should
run is in the compiled program as a ``tpu_custom_call``, and how far the
outputs are from the same cell on the jnp oracles (``kernels/ref.py``) run
on the same chip at ``highest`` matmul precision.  One JSON line per
phase; the last line is the verdict with the device JAX reports.  The
script exits non-zero on any failure, and without a verdict line when JAX
finds no TPU or the ``repro`` package is not beside it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import re
import shutil
import sys
import time
import traceback
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
OUT = ROOT / "chiprun_out"
SEED = 0

# Limits on the deviation from the oracle run (same cell, same chip,
# use_pallas=False, matmul precision "highest"), with the reason for each.
TOL = {
    # The kernels run their f32 layer GEMMs at the TPU's default matmul
    # precision (bf16 passes) while the oracle runs them in full f32:
    # ~1e-3 relative per step, compounded over E = 5 epochs x 3 rounds.
    "losses_rel": 2e-2,
    # A perturbed update can flip a coordinate across the Top-K threshold,
    # which moves that coordinate of the fog mean by up to the threshold
    # magnitude (~1e-3 for these models); 10x headroom.
    "params_abs": 1e-2,
    # F1 over 25,600 test points: a few flags near the threshold may move.
    "f1_abs": 2e-2,
    # Energy and participation depend on geometry, payload sizes and the
    # PRNG only, never on model values: equal up to f32 reassociation.
    "physics_rel": 1e-5,
    # Reconstruction error is a difference of near-equal vectors, so the
    # bf16-pass GEMMs' ~2e-3 relative error on x is amplified in it.
    "score_rel": 5e-2,
}
# The 4-chip cell against one device: the same kernels, only the order of
# the fog sums differs (psum of 4 partial sums vs one sequential pass), and
# a reordered sum can flip a coordinate across the Top-K threshold.
SHARD_TOL = {"losses_rel": 1e-3, "params_abs": 1e-3, "f1_abs": 1e-2,
             "physics_rel": 1e-5}


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The paper deployment (Table II) and the Table III fleet scale."""

    n: int = 200                 # sensors
    n_fog: int = 20
    n_fleet: int = 2000          # chunked / sharded fleet, n_fog = n / 10
    chunk: int = 512
    rounds: int = 3
    local_epochs: int = 5
    train_len: int = 256
    val_len: int = 64
    test_len: int = 128
    requests: int = 300


def fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def emit(line: dict) -> None:
    print(json.dumps(line, default=float), flush=True)


def kernels_in(text: str) -> set[str]:
    """Names of the Pallas kernels (``tpu_custom_call`` instructions, named
    after their ``kernels/*`` wrapper) in compiled HLO text."""
    return set(re.findall(
        r"%([A-Za-z_]\w*?)(?:\.\d+)* = [^\n]*custom_call_target=\"tpu_custom_call\"",
        text,
    ))


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-12)))


def _abs(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b))) if a.size else 0.0


def deviation(got: dict, ref: dict) -> dict:
    """Largest deviation of one trial's metrics from its reference."""
    import jax

    dev = {
        "losses_rel": _rel(got["losses"], ref["losses"]),
        "f1_abs": _abs(got["f1"], ref["f1"]),
        "physics_rel": max(_rel(got[k], ref[k])
                           for k in ("e_total", "participation")),
    }
    if "params" in got and "params" in ref:
        dev["params_abs"] = max(
            _abs(a, b) for a, b in zip(jax.tree_util.tree_leaves(got["params"]),
                                       jax.tree_util.tree_leaves(ref["params"]))
        )
    return dev


def check(phase: dict, dev: dict, tol: dict) -> None:
    over = {k: (v, tol[k]) for k, v in dev.items() if not v <= tol[k]}
    phase.setdefault("max_dev", {}).update(dev)
    if over:
        raise AssertionError(f"deviation beyond tolerance: {over}")


class Smoke:
    def __init__(self, sizes: Sizes):
        import jax

        from repro.checkpoint import CheckpointStore
        from repro.core import faults as flt
        from repro.data.synthetic import SyntheticConfig, generate, normalize
        from repro.engine import Engine
        from repro.launch import experiment as exp

        self.jax, self.exp, self.flt, self.Engine = jax, exp, flt, Engine
        self.sz = sizes

        def data(n):
            cfg = SyntheticConfig(n_sensors=n, train_len=sizes.train_len,
                                  val_len=sizes.val_len,
                                  test_len=sizes.test_len)
            return normalize(generate(jax.random.key(SEED), cfg))

        self.data = data
        self.ds = data(sizes.n)
        # Paper compressor defaults: rho_s = 0.05, int8 codes.
        self.cfg = exp.make_config(
            n_sensors=sizes.n, n_fog=sizes.n_fog, rounds=sizes.rounds,
            local_epochs=sizes.local_epochs,
        )
        ckpt = OUT / "smoke_ckpt"
        shutil.rmtree(ckpt, ignore_errors=True)
        self.store = CheckpointStore(str(ckpt))
        self.refs: dict = {}
        self.services: dict = {}

    # -- helpers ---------------------------------------------------------

    def assert_compiled_backend(self, eng, cfg) -> None:
        rc = eng.resolve_config(cfg)
        for name, knob in (("compressor", rc.compressor),
                           ("local_solver", rc.local_solver)):
            if not (knob.use_pallas and not knob.interpret):
                raise AssertionError(
                    f"{name} resolved to use_pallas={knob.use_pallas}, "
                    f"interpret={knob.interpret}"
                )

    def assert_kernels(self, phase: dict, text: str, expected: set) -> None:
        found = kernels_in(text)
        phase["kernels"] = sorted(found)
        missing = expected - found
        if missing:
            raise AssertionError(f"kernels missing from the program: {missing}")

    def run_cell(self, eng, method, cfg, ds, phase, **kw):
        """Engine.run twice: the first call compiles, the second reuses the
        program, so compile and run time are reported apart."""
        first = eng.run(method, cfg, (SEED,), ds, **kw)
        res = eng.run(method, cfg, (SEED,), ds, **kw)
        phase["compile_s"] = first.wall_s - res.wall_s
        phase["run_s"] = res.wall_s
        return res

    def oracle(self, eng, method, cfg, ds):
        """The same trial on the jnp oracles, full f32 matmuls."""
        jax, exp = self.jax, self.exp
        rc = eng.resolve_config(cfg)
        rc = rc.replace(
            compressor=rc.compressor.replace(use_pallas=False),
            local_solver=rc.local_solver.replace(use_pallas=False),
        )
        key = (method, repr(rc), ds.train.shape)
        if key not in self.refs:
            with jax.default_matmul_precision("highest"):
                fn = jax.jit(lambda k, d: exp.trial_metrics(
                    method, k, d, rc, return_params=True))
                self.refs[key] = jax.block_until_ready(
                    fn(jax.random.key(SEED), ds))
        return self.refs[key]

    def trial0(self, metrics: dict) -> dict:
        """The (seed, deployment) = (0, 0) trial of an (S, P) result."""
        return self.jax.tree_util.tree_map(lambda a: a[0, 0], metrics)

    # -- phases ----------------------------------------------------------

    def train(self, phase: dict) -> None:
        eng = self.Engine()
        self.assert_compiled_backend(eng, self.cfg)
        phase["shape"] = dict(n=self.sz.n, n_fog=self.sz.n_fog,
                              d=1352, rounds=self.sz.rounds)
        res = self.run_cell(eng, "hfl-selective", self.cfg, self.ds, phase,
                            store=self.store)
        self.assert_kernels(phase, eng.compiled()[0].as_text(),
                            {"local_train_blocks", "compress_aggregate_blocks"})
        got = self.trial0(res.metrics)
        ref = self.oracle(eng, "hfl-selective", self.cfg, self.ds)
        self.params_like = ref["params"]
        published, step = self.store.restore(self.params_like)
        phase["published_step"] = step
        got["params"] = published
        check(phase, deviation(got, ref), TOL)
        # Services watching the store: they load this round now and must
        # hot-swap to the robust phase's round in the serve phase.
        from repro.serving.service import ScoringService

        x = np.asarray(self.ds.test).reshape(-1, self.ds.test.shape[-1])
        self.tau = float(np.percentile(self._ref_err(published, x), 95.0))
        for dtype in ("f32", "int8"):
            self.services[dtype] = ScoringService(
                self.store, ref["params"], buckets=(128, 1024), tau=self.tau,
                weight_dtype=dtype, poll_every=10**9,
            )

    def train_chunked(self, phase: dict) -> None:
        n = self.sz.n_fleet
        eng = self.Engine(client_chunk=self.sz.chunk)
        cfg = self.exp.make_config(
            n_sensors=n, n_fog=n // 10, rounds=self.sz.rounds,
            local_epochs=self.sz.local_epochs, compressor=self.cfg.compressor,
        )
        self.assert_compiled_backend(eng, cfg)
        ds = self.data(n)
        phase["shape"] = dict(n=n, n_fog=n // 10, chunk=self.sz.chunk,
                              d=1352, rounds=self.sz.rounds)
        res = self.run_cell(eng, "hfl-selective", cfg, ds, phase)
        self.assert_kernels(phase, eng.compiled()[0].as_text(),
                            {"local_train_blocks", "compress_wire_blocks",
                             "wire_aggregate_blocks"})
        ref = self.oracle(eng, "hfl-selective", cfg, ds)
        check(phase, deviation(self.trial0(res.metrics), ref), TOL)

    def robust(self, phase: dict) -> None:
        eng = self.Engine()
        cfg = self.cfg.replace(
            robust="trimmed", trim_frac=0.1,
            faults=self.flt.FaultConfig(erasure_prob=0.1),
        )
        self.assert_compiled_backend(eng, cfg)
        phase["shape"] = dict(n=self.sz.n, n_fog=self.sz.n_fog, d=1352,
                              rounds=self.sz.rounds, trim_frac=0.1,
                              erasure_prob=0.1)
        res = self.run_cell(eng, "hfl-selective", cfg, self.ds, phase,
                            store=self.store, publish_step=self.sz.rounds + 1)
        self.assert_kernels(phase, eng.compiled()[0].as_text(),
                            {"local_train_blocks", "compress_aggregate_blocks",
                             "robust_aggregate_blocks"})
        ref = self.oracle(eng, "hfl-selective", cfg, self.ds)
        got = self.trial0(res.metrics)
        got["params"] = self.store.restore(ref["params"],
                                           step=self.sz.rounds + 1)[0]
        check(phase, deviation(got, ref), TOL)

    def sweep(self, phase: dict) -> None:
        eng = self.Engine()
        cells = [self.cfg.replace(compressor=self.cfg.compressor.replace(
            rho_s=r)) for r in (0.05, 0.1)]
        for c in cells:
            self.assert_compiled_backend(eng, c)
        phase["shape"] = dict(n=self.sz.n, n_fog=self.sz.n_fog, d=1352,
                              rho_s=[0.05, 0.1], rounds=self.sz.rounds)
        first = eng.sweep("hfl-selective", cells, (SEED,), self.ds)
        sw = eng.sweep("hfl-selective", cells, (SEED,), self.ds)
        phase["classes"] = sw.n_classes
        phase["compile_s"] = first.wall_s - sw.wall_s
        phase["run_s"] = sw.wall_s
        if sw.n_classes != 2:
            raise AssertionError(
                f"kernel-bound rho_s must split the sweep into 2 classes, "
                f"got {sw.n_classes}"
            )
        for comp in eng.compiled():
            self.assert_kernels(phase, comp.as_text(),
                                {"local_train_blocks",
                                 "compress_aggregate_blocks"})
        devs = [deviation(self.trial0(sw.cell(i)),
                          self.oracle(eng, "hfl-selective", c, self.ds))
                for i, c in enumerate(cells)]
        check(phase, {k: max(d[k] for d in devs) for k in devs[0]}, TOL)

    def _ref_err(self, params, x, quantized=False):
        from repro.kernels import ref as kref
        from repro.serving.score import quantize_params

        jax = self.jax
        with jax.default_matmul_precision("highest"):
            if quantized:
                qp = quantize_params(params)
                err, _ = kref.fused_score_q8_ref(
                    x, tuple(q["qw"] for q in qp), tuple(q["sw"] for q in qp),
                    tuple(q["b"] for q in qp), np.zeros(len(x), np.float32))
            else:
                err, _ = kref.fused_score_ref(
                    x, tuple(p["w"] for p in params),
                    tuple(p["b"] for p in params),
                    np.zeros(len(x), np.float32))
        return np.asarray(err)

    def serve(self, phase: dict, dtype: str) -> None:
        # The package re-exports a function named ``score``: import from
        # the submodule itself.
        from repro.serving.score import default_use_pallas

        if not default_use_pallas():
            raise AssertionError("the scoring service resolved the jnp oracle")
        svc = self.services[dtype]
        if not svc.poll() or svc.loaded_step != self.sz.rounds + 1:
            raise AssertionError(
                f"hot-swap to the robust round failed (loaded step "
                f"{svc.loaded_step})"
            )
        params, _ = self.store.restore(self.params_like, step=svc.loaded_step)
        d = self.ds.test.shape[-1]
        test = np.asarray(self.ds.test)               # (N, T, d)
        rng = np.random.default_rng(SEED)
        reqs = []
        for i in range(self.sz.requests):
            rows = int(rng.integers(1, 17))
            start = int(rng.integers(0, test.shape[1] - rows + 1))
            reqs.append(test[i % test.shape[0], start:start + rows])
        small = [test[i % test.shape[0], :3] for i in range(20)]

        # Warm-up: one batch per bucket compiles both programs.
        t0 = time.perf_counter()
        svc.submit(test[0, :1])
        svc.drain()
        for r in small[:2]:
            svc.submit(r)
        for _ in range(2):
            svc.submit(test[:8].reshape(-1, d)[:1024])
        svc.drain()
        phase["compile_s"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        rids = [svc.submit(r) for r in reqs]
        done = svc.drain()
        rids += [svc.submit(r) for r in small]
        done.update(svc.drain())
        phase["run_s"] = time.perf_counter() - t0
        phase["shape"] = dict(requests=len(rids),
                              rows=int(sum(len(r) for r in reqs + small)),
                              buckets=[128, 1024], d=d, weights=dtype)
        phase["stats"] = {k: svc.stats.summary()[k] for k in
                          ("steps", "swaps", "compiles_by_bucket",
                           "step_p50_ms", "step_p99_ms")}
        if set(svc.stats.compiles_by_bucket) != {128, 1024}:
            raise AssertionError(
                f"both buckets must run, got {svc.stats.compiles_by_bucket}")

        expected = "score_blocks_q8" if dtype == "int8" else "score_blocks"
        for b in (128, 1024):
            text = svc.programs.fn(b).lower(
                svc.params, np.zeros((b, d), np.float32),
                np.zeros((b,), np.float32)).compile().as_text()
            self.assert_kernels(phase, text, {expected})

        rows = np.concatenate(reqs + small)
        err_ref = self._ref_err(params, rows, quantized=dtype == "int8")
        err = np.concatenate([np.asarray(done[i].error).reshape(-1)
                              for i in rids])
        flag = np.concatenate([np.asarray(done[i].flag).reshape(-1)
                               for i in rids])
        # A flag may differ from the oracle's only where the oracle's own
        # error sits within the score tolerance of the threshold.
        near = np.abs(err_ref - self.tau) <= TOL["score_rel"] * self.tau
        flips = int(np.sum((flag != (err_ref > self.tau)) & ~near))
        phase["flags_set"] = int(np.sum(flag))
        check(phase, {"score_rel": _rel(err, err_ref)}, TOL)
        if flips:
            raise AssertionError(f"{flips} flags differ away from tau")

    def sharded(self, phase: dict) -> None:
        jax = self.jax
        n = self.sz.n_fleet
        n_dev = len(jax.devices())
        cfg = self.exp.make_config(
            n_sensors=n, n_fog=n // 10, rounds=self.sz.rounds,
            local_epochs=self.sz.local_epochs, compressor=self.cfg.compressor,
        )
        ds = self.data(n)
        phase["shape"] = dict(n=n, n_fog=n // 10, d=1352, devices=n_dev,
                              rounds=self.sz.rounds)
        eng = self.Engine(shard_clients=True)
        self.assert_compiled_backend(eng, cfg)
        res = self.run_cell(eng, "hfl-selective", cfg, ds, phase)
        if not eng.take_log()[-1]["client_sharded"]:
            raise AssertionError("the cell did not run client-sharded")
        (comp,) = eng.compiled()
        text = comp.as_text()
        self.assert_kernels(phase, text, {"local_train_blocks",
                                          "compress_aggregate_blocks"})
        phase["all_reduce"] = text.count(" all-reduce(")
        if not phase["all_reduce"]:
            raise AssertionError("no all-reduce of the fog buffers")
        ds_shardings = jax.tree_util.tree_leaves(comp.input_shardings[0][1])
        spread = {len(s.device_set) for s in ds_shardings}
        if spread != {n_dev} or any(
            s.spec[1] != "data" for s in ds_shardings
        ):
            raise AssertionError(
                f"sensor-axis inputs not sharded over {n_dev} devices: "
                f"{[str(s) for s in ds_shardings]}"
            )
        phase["input_sharding"] = str(ds_shardings[0].spec)
        one = self.Engine(shard_trials=False)
        ref_phase: dict = {}
        ref = self.run_cell(one, "hfl-selective", cfg, ds, ref_phase)
        phase["one_device_run_s"] = ref_phase["run_s"]
        check(phase, deviation(self.trial0(res.metrics),
                               self.trial0(ref.metrics)), SHARD_TOL)


def run_phases(smoke: Smoke, phases) -> bool:
    ok = True
    for name, fn in phases:
        phase: dict = {"phase": name}
        t0 = time.perf_counter()
        try:
            fn(phase)
            phase["ok"] = True
        except Exception as e:  # report every phase, then fail overall
            ok = False
            phase["ok"] = False
            phase["error"] = f"{type(e).__name__}: {e}"[:2000]
            traceback.print_exc(file=sys.stderr)
        phase["wall_s"] = time.perf_counter() - t0
        emit(phase)
        with open(OUT / "chip_smoke.jsonl", "a") as f:
            f.write(json.dumps(phase, default=float) + "\n")
    return ok


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the client-sharded cell over 4 chips")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX found {devices[0].platform} devices only")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} TPU chips, JAX found "
             f"{len(devices)}")
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch import compile_cache
    except ImportError as e:
        fail(f"the repro package is not beside this script ({e})")
    compile_cache.enable()
    OUT.mkdir(exist_ok=True)
    (OUT / "chip_smoke.jsonl").unlink(missing_ok=True)

    smoke = Smoke(Sizes())
    if args.chips == 4:
        phases = [("sharded-4chip", smoke.sharded)]
    else:
        phases = [
            ("train", smoke.train),
            ("train-chunked", smoke.train_chunked),
            ("robust", smoke.robust),
            ("sweep", smoke.sweep),
            ("serve-f32", lambda p: smoke.serve(p, "f32")),
            ("serve-int8", lambda p: smoke.serve(p, "int8")),
        ]
    ok = run_phases(smoke, phases)
    d = jax.devices()
    emit({"ok": ok, "device": {"platform": d[0].platform,
                               "kind": d[0].device_kind, "count": len(d)}})
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
