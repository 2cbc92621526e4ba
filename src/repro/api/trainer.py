"""``api.Trainer`` — the thin host-side driver every entry point shares.

The trainer owns the *host* side of an experiment: synthesizing the
dataset from :class:`repro.api.DataSpec`, assembling per-round batches
(eq. 3 sizing via :mod:`repro.data.loader`; image rows stay on the
device and a round uploads only the row ids it drew), threading the
:class:`repro.api.build.ProgramState` through the built
:class:`repro.api.build.RoundProgram`, and evaluation. Everything
jit-compiled lives in the program; everything numpy lives here.

    spec = api.ExperimentSpec(...)           # declarative, serializable
    trainer = api.Trainer(spec)              # build(spec) + data + state
    history = trainer.run()                  # spec.rounds rounds/events
    print(trainer.evaluate())

Host-side RNG choreography is kept exactly as the pre-API drivers'
(``numpy.default_rng(seed + 7)`` for image data / client sampling as in
``benchmarks/common.run_experiment``; ``default_rng(seed)`` for the LM
driver as in ``launch/train.py``), so existing results reproduce.

Batch-budget parity across modes follows each driver's convention too:
for ``image_synthetic`` the in-program sync modes (masked/sparse) split
``server_batch / participation`` over all K slots so the participating
subset sees ~``server_batch`` samples (eq. 3 parity with subset mode);
for ``lm_synthetic`` the budget is never rescaled (scale
``server_batch`` by 1/FRAC yourself for parity — the historical
``train.py`` semantics).
"""
from __future__ import annotations

import json
import os
import time
from functools import partial
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.build import RoundProgram, build
from repro.api.specs import ExperimentSpec
from repro.perf import trace


# ---------------------------------------------------------------------------
# dataset synthesis (host side)
# ---------------------------------------------------------------------------


def build_lm_data(cfg, num_clients: int, docs_per_client: int, seq: int,
                  seed: int) -> List[np.ndarray]:
    """Domain-skewed synthetic token docs: client k prefers domain k % D."""
    from repro.data.synthetic import token_stream

    docs, domains = token_stream(
        n_docs=num_clients * docs_per_client, doc_len=seq + 1,
        vocab=cfg.vocab_size, num_domains=max(2, num_clients // 2), seed=seed)
    rng = np.random.default_rng(seed + 1)
    by_client = []
    D = domains.max() + 1
    for k in range(num_clients):
        pref = k % D
        p = np.where(domains == pref, 8.0, 1.0)
        p = p / p.sum()
        idx = rng.choice(len(docs), size=docs_per_client, replace=False, p=p)
        by_client.append(docs[idx])
    return by_client


def build_image_data(spec: ExperimentSpec):
    """CIFAR-shaped gaussian images, label-skew partitioned per DataSpec.

    Returns (FederatedData, (x_test, y_test))."""
    from repro.data.loader import FederatedData
    from repro.data.partition import partition
    from repro.data.synthetic import gaussian_images

    d = spec.data
    x, y = gaussian_images(d.n_train + d.n_test, num_classes=d.num_classes,
                           seed=spec.seed)
    x_train, y_train = x[:d.n_train], y[:d.n_train]
    parts = partition(y_train, spec.scala.num_clients, alpha=d.alpha,
                      beta=d.beta, num_classes=d.num_classes, seed=spec.seed)
    return (FederatedData.from_partition(x_train, y_train, parts),
            (x[d.n_train:], y[d.n_train:]))


@partial(jax.jit, static_argnums=3)
def _gather_rows(pool_x, pool_y, rows, row_shape):
    """A round's image batches from the device pool (``pool_x`` holds
    one flat row per image): exactly what ``round_batches`` assembles on
    the host for the same rows."""
    return {"x": pool_x[rows].reshape(rows.shape + row_shape),
            "labels": pool_y[rows],
            "weights": (rows < pool_y.shape[0] - 1).astype(jnp.float32)}


# ---------------------------------------------------------------------------
# the trainer
# ---------------------------------------------------------------------------


class Trainer:
    """Run a built experiment round by round.

    ``program`` defaults to :func:`repro.api.build` on the spec;
    pass one explicitly to reuse a compiled program across trainers
    (sweeps over data seeds) or to inject ``mesh``/``batch_specs`` for
    the ``lace_dp`` backend.
    """

    def __init__(self, spec: ExperimentSpec, *,
                 program: Optional[RoundProgram] = None,
                 mesh=None, batch_specs=None):
        self.spec = spec.validate()
        self.program = program if program is not None else build(
            spec, mesh=mesh, batch_specs=batch_specs)
        self.state = self.program.init()
        self.history: List[Dict[str, float]] = []
        self.round = 0
        # bytes handed to jnp.asarray: for image data the pool once, then
        # each round's rows and sizes; for LM data each round's batches
        self.upload_bytes = 0
        # bytes of the image pool on the device; rounds gathered from it
        self.resident_bytes = 0
        self.gathered_rounds = 0
        self._pool = self._pool_of = None
        self._rpc = self.program.metadata.get("rounds_per_call", 1)
        self._cfg = spec.model_config()
        if spec.data.kind == "image_synthetic":
            self._data, self._test = build_image_data(spec)
            self._rng = np.random.default_rng(spec.seed + 7)
        else:
            self._data = build_lm_data(self._cfg, spec.scala.num_clients,
                                       spec.data.docs_per_client,
                                       spec.data.seq, spec.seed)
            self._test = None
            self._rng = np.random.default_rng(spec.seed)

    # ------------------------------------------------------------------

    def _draw_round(self):
        """One round's host draw, in the RNG order of the pre-API
        drivers: (rows, sizes) for image data, rows being global ids into
        the device pool; (batches, sizes) for LM data."""
        from repro.data.loader import (lm_round_batches, round_rows,
                                       sample_clients)

        spec, sc = self.spec, self.spec.scala
        K = sc.num_clients
        if spec.execution.in_program:
            selected = np.arange(K)        # all slots; subset in-program
        else:
            selected = sample_clients(K, sc.clients_per_round, self._rng)
        if spec.data.kind == "image_synthetic":
            budget = (round(sc.server_batch / sc.participation)
                      if spec.execution.mode in ("masked", "sparse")
                      else sc.server_batch)
            return round_rows(self._data, selected, budget, sc.local_iters,
                              self._rng)
        rb = lm_round_batches(self._data, selected, sc.server_batch,
                              sc.local_iters, self._rng)
        return rb, rb.pop("sizes")

    def _upload(self, a: np.ndarray):
        self.upload_bytes += a.nbytes
        return jnp.asarray(a)

    def _device_pool(self):
        """The image clients' rows on the device (``pool_arrays``), built
        on first use and again whenever ``_data`` is replaced."""
        from repro.data.loader import pool_arrays

        if self._pool_of is not self._data:
            self._pool = self._pool_of = None    # free the old pool first
            x, y = pool_arrays(self._data)
            # flat rows: the TPU lays out (N + 1, H, W, C) with N minor,
            # which a row gather would have to relayout whole every call
            self._pool = (self._upload(x.reshape(len(x), -1)),
                          self._upload(y), x.shape[1:])
            self._pool_of = self._data
            self.resident_bytes = sum(a.nbytes for a in self._pool[:2])
        return self._pool

    def _next_round_batches(self, rounds: Optional[int] = None):
        """Device batches and sizes of one round, or of ``rounds`` rounds
        stacked along a leading round axis. Image rounds upload only
        their row ids and gather the batch from the device pool."""
        draws = [self._draw_round() for _ in range(rounds or 1)]
        stack = (lambda xs: xs[0]) if rounds is None else np.stack
        sizes = self._upload(stack([s for _, s in draws]))
        if self.spec.data.kind == "image_synthetic":
            rows = self._upload(stack([r for r, _ in draws]))
            self.gathered_rounds += len(draws)
            pool_x, pool_y, row_shape = self._device_pool()
            return _gather_rows(pool_x, pool_y, rows, row_shape), sizes
        return ({k: self._upload(stack([b[k] for b, _ in draws]))
                 for k in draws[0][0]}, sizes)

    # ------------------------------------------------------------------

    def step(self, rounds: Optional[int] = None):
        """One program dispatch: assemble batches, advance state.

        With ``execution.rounds_per_call = 1`` (the default) this is one
        round (or async event). With ``R > 1`` one dispatch executes
        ``min(R, rounds)`` whole rounds fused into a single XLA program:
        the per-round batches are assembled host-side in exactly the
        order the unfused path would draw them (same RNG stream), stacked
        along a leading round axis, and the stacked metrics are pulled to
        host once. One history entry is appended per *round* either way.

        Returns the last executed round's scalar metrics as floats."""
        n = self._rpc if rounds is None else min(rounds, self._rpc)
        if self._rpc == 1:
            with trace.span("trainer.batches"):
                batches, sizes = self._next_round_batches()
            self.state, metrics = self.program.step(self.state, batches,
                                                    sizes)
            with trace.span("trainer.sync"):
                scalars = {k: float(v) for k, v in metrics.items()
                           if jnp.ndim(v) == 0}
            self.history.append(scalars)
            self.round += 1
            return scalars
        with trace.span("trainer.batches"):
            batches, sizes = self._next_round_batches(n)
        self.state, metrics = self.program.step(self.state, batches, sizes)
        # per-round scalars carry the leading (n,) round axis now; ONE
        # device-to-host pull per metric for the whole chunk
        with trace.span("trainer.sync"):
            stacked = {k: np.asarray(v) for k, v in metrics.items()
                       if jnp.ndim(v) == 1}
        scalars = None
        for r in range(n):
            scalars = {k: float(v[r]) for k, v in stacked.items()}
            self.history.append(scalars)
        self.round += n
        return scalars

    def run(self, rounds: Optional[int] = None, *,
            on_round: Optional[Callable[[int, Dict[str, float], float],
                                        Any]] = None):
        """Run ``rounds`` rounds (default ``spec.rounds``); returns the
        full metric history (one dict of floats per round so far).
        ``on_round(index, metrics, seconds)`` is called for every round;
        under ``rounds_per_call`` fusion it fires once per round after
        each chunk, with ``seconds`` amortized over the chunk (fuse less
        if you need a true per-round host callback). A trailing
        remainder chunk (``rounds % rounds_per_call``) just recompiles
        the step once for the smaller leading axis."""
        n = self.spec.rounds if rounds is None else rounds
        done = 0
        while done < n:
            k = min(self._rpc, n - done)
            t0 = time.perf_counter()
            self.step(k)
            dt = time.perf_counter() - t0
            done += k
            if on_round is not None:
                for j in range(k):
                    on_round(self.round - k + j,
                             self.history[len(self.history) - k + j],
                             dt / k)
        return self.history

    # ------------------------------------------------------------------
    # crash recovery
    # ------------------------------------------------------------------

    def save(self, directory: str) -> str:
        """Checkpoint the FULL run: program state + host driver state.

        The ``.npz`` carries the whole :class:`ProgramState` pytree —
        params, optimizer moments, and the fed state (sync dict or
        :class:`AsyncFedState` including ring/delta snapshots, finish
        times, versions, retries, fault keys, guard medians). A
        ``meta_{round}.json`` sidecar carries the host side: round
        counter, metric history, and the numpy bit-generator state that
        drives batch assembly. Both writes are atomic
        (write-temp-fsync-rename), so a crash mid-save never corrupts an
        existing checkpoint. :meth:`resume` from the pair is bit-identical
        to never having stopped."""
        if self.program.metadata.get("host_paged"):
            raise ValueError(
                "save/resume with opt_paging='host' is unsupported: the "
                "paged optimizer moments live in the host pager, outside "
                "ProgramState; keep optimizer state on device to "
                "checkpoint")
        from repro import checkpoint as C

        path = C.save(directory, self.round, self.state)
        C.write_json_atomic(
            os.path.join(directory, f"meta_{self.round:08d}.json"),
            {"round": self.round, "history": self.history,
             "rng_state": self._rng.bit_generator.state})
        return path

    def resume(self, directory: str, step: Optional[int] = None) -> int:
        """Restore the newest complete checkpoint; returns its round.

        A checkpoint counts only when both its ``.npz`` and its
        ``meta_{round}.json`` sidecar are readable — a torn pair from a
        crash mid-save is skipped and the next-older step is tried
        (unless ``step`` pins one explicitly, which raises instead).
        After resume, :meth:`run`/:meth:`step` continue the interrupted
        RNG stream and program state exactly."""
        from repro import checkpoint as C

        candidates = [step] if step is not None else C.all_steps(directory)[::-1]
        if not candidates:
            raise FileNotFoundError(f"no checkpoints in {directory}")
        for s in candidates:
            meta_path = os.path.join(directory, f"meta_{s:08d}.json")
            try:
                with open(meta_path) as f:
                    meta = json.load(f)
                state = C.restore(directory, self.state, step=s)
            except C.CORRUPT_ERRORS + (json.JSONDecodeError,
                                       AssertionError):
                if step is not None:
                    raise
                continue
            break
        else:
            raise FileNotFoundError(
                f"no complete (npz + meta) checkpoint in {directory}")
        self.state = state
        self.round = int(meta["round"])
        self.history = list(meta["history"])
        self._rng.bit_generator.state = meta["rng_state"]
        return self.round

    # ------------------------------------------------------------------

    def evaluate(self) -> Dict[str, float]:
        """Evaluate the current global model.

        image_synthetic — held-out accuracy + class-balanced accuracy
        (the paper-table metrics); lm_synthetic — next-token loss and
        accuracy on a held-out document stream (seeded off the
        experiment seed)."""
        from repro.core.losses import (accuracy, per_class_accuracy,
                                       softmax_xent)

        spec = self.spec
        if spec.data.kind == "image_synthetic":
            x_test, y_test = self._test
            logits = self.program.predict(self.state,
                                          {"x": jnp.asarray(x_test)})
            y = jnp.asarray(y_test)
            return {"acc": float(accuracy(logits, y)),
                    "balanced_acc": float(per_class_accuracy(
                        logits, y, spec.data.num_classes))}
        from repro.data.synthetic import token_stream

        docs, _ = token_stream(
            n_docs=32, doc_len=spec.data.seq + 1,
            vocab=self._cfg.vocab_size,
            num_domains=max(2, spec.scala.num_clients // 2),
            seed=spec.seed + 9973)
        toks = jnp.asarray(docs[:, :-1])
        labels = jnp.asarray(docs[:, 1:])
        logits = self.program.predict(self.state, {"tokens": toks})
        return {"eval_loss": float(softmax_xent(logits, labels)),
                "eval_accuracy": float(accuracy(logits, labels))}
