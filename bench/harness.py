"""The benchmark harness: finds a cell's files by name, builds the
program through the repo's own entry points, drives its first rounds
for the correctness check, times the window and reduces the trace.

Everything a cell needs is found by name from ``BENCHMARK.json``:

* ``bench/configs/<config>.json``: the model, its sizes and its
  numerics, the reference family (``bench/models/<family>.py``) and how
  the spec is made (the argv of ``launch/train.py`` or the fields of an
  ``api.ExperimentSpec``);
* ``bench/traffic/<traffic>.json``: the federated job (clients,
  participation, mode, batch, sequence, local steps, optimizer), the
  data generator's parameters when the benchmark makes the data, and
  the work one round must do;
* ``bench/limits/<workload>.json``: the limit of each number compared;
* ``bench/metrics/<metric>.py``: one reader per per-layer metric.
"""
from __future__ import annotations

import dataclasses
import gc
import hashlib
import importlib
import importlib.util
import json
import statistics
import sys
import time
from functools import partial
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import scala_ref
from bench.scala_ref import Frozen

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CHECK_ROUNDS = 3
# length of the traced window of a --trace 1 run (traces are large)
TRACE_SECONDS = 5.0
# leaves whose reference first-round update is below this share of the
# median leaf's move by round-off alone (e.g. attention key biases,
# whose gradient softmax cancels) and are not compared
NOUGHT = 1e-3


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# finding a cell's files by name
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    workload: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    family: ModuleType
    benchmark: Dict[str, Any]

    @property
    def name(self) -> str:
        return self.workload["name"]

    def metrics(self, kind: str) -> List[Dict[str, Any]]:
        """The cell's ``end_to_end`` or ``per_layer`` metric entries."""
        return [m for m in self.benchmark[kind]
                if self.name in m.get("workloads", [self.name])]


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bm = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    bench = root / "bench"
    config = load_json(bench / "configs" / f"{w['config']}.json")
    traffic = load_json(bench / "traffic" / f"{w['traffic']}.json")
    limits_path = bench / "limits" / f"{name}.json"
    limits = load_json(limits_path)["limits"] if limits_path.exists() else {}
    family = importlib.import_module(f"bench.models.{config['family']}")
    return Cell(w, config, traffic, limits, family, bm)


def metric_reader(name: str, root: Path = ROOT):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# the spec, through the repo's own entry points
# ---------------------------------------------------------------------------


def _merge(a: Dict[str, Any], b: Dict[str, Any]) -> Dict[str, Any]:
    out = dict(a)
    for k, v in b.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


def make_spec(cell: Cell, seed: int, **execution):
    """The cell's ``api.ExperimentSpec`` for ``seed``. ``execution``
    overrides fields of its ``ExecutionSpec`` (the control's precision)."""
    from repro import api

    c, t = cell.config["spec"], cell.traffic["spec"]
    if c["kind"] == "train_argv":
        from repro.launch import train as cli

        argv = c["argv"] + t["argv"] + ["--seed", str(seed)]
        spec = cli.spec_from_args(cli.build_parser().parse_args(argv))
    elif c["kind"] == "experiment":
        spec = api.ExperimentSpec.from_dict(
            _merge(_merge(c["fields"], t["fields"]), {"seed": seed}))
    else:
        raise ValueError(f"unknown spec kind {c['kind']!r}")
    if execution:
        spec = dataclasses.replace(spec, execution=dataclasses.replace(
            spec.execution, **execution))
    _check_reference_terms(cell, spec, bool(execution))
    mc = spec.model_config()
    for k, v in cell.config["program_config"].items():
        bad = config_difference(k, getattr(mc, k), v)
        if bad:
            raise ValueError(f"{cell.config['name']}: the program's {bad}")
    return spec


def config_difference(name: str, got, want) -> Optional[str]:
    """How the program's config value ``got`` differs from the file's
    ``want`` (None where it does not). A dict in the file is a nested
    sub-config (``moe``, ``mamba``, ``xlstm``): each of its keys is
    compared with that field of the program's dataclass."""
    if isinstance(want, dict):
        if dataclasses.is_dataclass(got):
            got = dataclasses.asdict(got)
        if not isinstance(got, dict):
            return (f"{name} is {got!r}, the configuration file says "
                    f"{want!r}")
        for k, v in want.items():
            if k not in got:
                return (f"{name} has no field {k!r}; the configuration "
                        f"file says {v!r}")
            bad = config_difference(f"{name}.{k}", got[k], v)
            if bad:
                return bad
        return None
    if (list(got) if isinstance(got, tuple) else got) != want:
        return f"{name} is {got!r}, the configuration file says {want!r}"
    return None


def _check_reference_terms(cell: Cell, spec, overridden: bool) -> None:
    """The reference computes plain SCALA with SGD at a constant rate,
    data-size weighted aggregation and the configuration's tau, eps and
    precision: refuse a spec that asks for anything else."""
    ref, sc = cell.config["reference"], spec.scala
    want = {
        "method": (spec.method, "scala"),
        "optimizer": (spec.optim.name, "sgd"),
        "schedule": (spec.optim.schedule, "constant"),
        "weight_decay": (spec.optim.weight_decay, 0.0),
        "lr": (spec.optim.resolve_lr(sc.lr), cell.traffic["lr"]),
        "aggregator": (spec.fed.aggregator, "weighted"),
        "server_optimizer": (spec.execution.server_optimizer, None),
        "faults": (spec.fed.faults, None),
        "guards": (spec.fed.guards, None),
        "tau": (sc.tau, ref["tau"]),
        "prior_eps": (sc.prior_eps, ref["prior_eps"]),
        "adjust": ((sc.adjust_server, sc.adjust_client), (True, True)),
        "label_smoothing": (sc.label_smoothing, 0.0),
    }
    if not overridden:
        want["precision"] = (spec.execution.precision,
                             cell.config["precision"])
    for k, (got, exp) in want.items():
        if got != exp:
            raise ValueError(f"{cell.name}: spec {k} is {got!r}; the "
                             f"reference computes {exp!r}")


# ---------------------------------------------------------------------------
# the program under test, its first rounds, and what they fed
# ---------------------------------------------------------------------------


class Recorder:
    """Wraps ``RoundProgram.step``: while ``on``, keeps host copies of
    what each call was fed and the participation masks of its rounds."""

    def __init__(self, step, masks_of):
        self.step = step
        self.masks_of = masks_of
        self.on = True
        self.calls: List[Dict[str, Any]] = []

    def __call__(self, state, batches, sizes):
        if not self.on:
            return self.step(state, batches, sizes)
        masks = self.masks_of(state)    # before the state is donated
        out = self.step(state, batches, sizes)
        self.calls.append(dict(masks=masks,
                               batches={k: np.asarray(v)
                                        for k, v in batches.items()},
                               sizes=np.asarray(sizes)))
        return out


@dataclasses.dataclass
class Readings:
    """What the first rounds did: per-round losses (eq. 14, eq. 15),
    per-leaf norms of the change of the global weights after the first
    call and after the last of the check calls, and (host copies) those
    global weights."""

    losses: List[List[float]]
    first: Dict[str, float]
    change: Dict[str, float]
    first_w: Any = None
    change_w: Any = None


@dataclasses.dataclass
class Fed:
    """The check rounds' inputs, participants only: per round the
    batches (leaves (T, m, rows, ...)) and sizes (m,); ``per_call``
    rounds ran in each ``Trainer.step`` call."""

    rounds: List[Dict[str, np.ndarray]]
    slots: int
    per_call: int = 1


def leaf_norms(canon) -> Dict[str, Any]:
    """Per-leaf L2 norms; leaves stacked under ``layers`` count per layer."""
    out = {}
    for path, a in jax.tree_util.tree_flatten_with_path(canon)[0]:
        name = jax.tree_util.keystr(path)
        if "'layers'" in name:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(a),
                                         axis=tuple(range(1, a.ndim))))
        else:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(a)))
    return out


def diff_norms(a, b) -> Dict[str, float]:
    """Per-leaf L2 norms of ``a - b`` (host trees), named as
    :func:`leaf_norms` names them, a stacked leaf's layers as
    ``name[i]``. Computed on the device: numpy takes close to a minute
    over the 0.6 billion weights of a full-width LM."""
    return _flat_norms(_diff_norms(a, b))


@jax.jit
def _diff_norms(a, b):
    return leaf_norms(jax.tree.map(jnp.subtract, a, b))


def _flat_norms(d) -> Dict[str, float]:
    out = {}
    for k, v in d.items():
        v = np.asarray(v, np.float64)
        if v.ndim:
            out.update({f"{k}[{i}]": float(x) for i, x in enumerate(v)})
        else:
            out[k] = float(v)
    return out


@partial(jax.jit, static_argnums=(0, 1))
def init_weights(family, config, key):
    return family.init_weights(config, key)


@partial(jax.jit, static_argnums=(0, 1, 2))
def program_weights(family, config, slots, key):
    return family.to_program(config, family.init_weights(config, key), slots)


@partial(jax.jit, static_argnums=(0, 1))
def from_program(family, config, params):
    return family.from_program(config, params)


@partial(jax.jit, static_argnums=(0, 1))
def _change(family, config, key, canon):
    w0 = family.init_weights(config, key)
    return leaf_norms(jax.tree.map(lambda a, b: a - b, canon, w0))


def weights_key(seed: int):
    return jax.random.fold_in(jax.random.PRNGKey(seed), 0x5CA1A)


def change_norms(family, config, seed: int, canon) -> Dict[str, float]:
    """Per-leaf norms of ``canon`` minus the seed's initial weights
    (made again from the seed, on the device)."""
    return _flat_norms(_change(family, Frozen(config), weights_key(seed),
                               canon))


class Job:
    """One cell at one seed: the spec, the built program, and the trainer
    that drives it (``api.Trainer`` on ``api.build``'s program)."""

    def __init__(self, cell: Cell, seed: int, program=None, **execution):
        from repro import api

        self.cell, self.seed = cell, seed
        self.spec = make_spec(cell, seed, **execution)
        self.program = program if program is not None else api.build(
            self.spec)
        self.recorder = Recorder(self.program.step, self._masks)
        self.trainer = api.Trainer(self.spec, program=dataclasses.replace(
            self.program, step=self.recorder))
        self._load_weights()
        data = cell.traffic.get("data")
        if data is not None:
            from bench import data as bench_data
            from repro.data.loader import FederatedData

            xs, ys = bench_data.make(data, seed)
            self.trainer._data = FederatedData(xs=xs, ys=ys)

    # -- inputs made by the benchmark ------------------------------------

    def _load_weights(self):
        """Replace the program's initial weights by the benchmark's, made
        from the seed on the device in one jitted call."""
        fam, cfg = self.cell.family, self.cell.config
        st = self.trainer.state
        old = st.inner.params
        new = program_weights(fam, Frozen(cfg), self.spec.slots,
                              weights_key(self.seed))
        if (jax.tree.structure(new) != jax.tree.structure(old)
                or jax.tree.map(lambda a: (a.shape, a.dtype), new)
                != jax.tree.map(lambda a: (a.shape, a.dtype), old)):
            raise ValueError(
                f"{cfg['name']}: the program's parameter layout differs "
                f"from bench/models/{cfg['family']}.py's")
        del old
        self.trainer.state = dataclasses.replace(
            st, inner=dataclasses.replace(st.inner, params=new))

    def _masks(self, state):
        """Participation masks of the rounds this call runs, from the
        program's scheduler state (its random draw; not computed here)."""
        spec = self.spec
        rpc = self.program.metadata.get("rounds_per_call", 1)
        if spec.execution.mode not in ("masked", "sparse"):
            raise ValueError(f"mode {spec.execution.mode!r} has no "
                             "per-round participation mask")
        sched = spec.fed.make_participation(spec.slots)
        s = state.fed["sched"]
        masks = []
        for _ in range(rpc):
            m, s = sched.sample(s)
            masks.append(np.asarray(m))
        return masks

    # -- the check rounds ---------------------------------------------------

    def check_rounds(self, calls: int = CHECK_ROUNDS):
        """Drive the first ``calls`` calls of ``Trainer.step`` (the
        window's own call and feed) and read what they did."""
        tr = self.trainer
        first = change = None
        for i in range(calls):
            tr.step()
            if i == 0:
                first = self._change()
            if i == calls - 1:
                change = self._change()
        self.recorder.on = False
        losses = [[h["loss_server"], h["loss_client"]] for h in tr.history]
        fed = self._fed()
        return Readings(losses, first[0], change[0], first[1], change[1]), fed

    def _change(self):
        fam, cfg = self.cell.family, self.cell.config
        canon = from_program(fam, Frozen(cfg), self.trainer.state.inner.params)
        return change_norms(fam, cfg, self.seed, canon), jax.device_get(canon)

    def _fed(self) -> Fed:
        rounds = []
        for call in self.recorder.calls:
            R = len(call["masks"])
            for r, mask in enumerate(call["masks"]):
                part = np.flatnonzero(mask > 0)
                pick = (lambda a: a[r]) if R > 1 else (lambda a: a)
                b = {k: pick(v)[:, part] for k, v in call["batches"].items()}
                b["sizes"] = pick(call["sizes"])[part]
                rounds.append(b)
        return Fed(rounds, self.spec.slots,
                   self.program.metadata.get("rounds_per_call", 1))

    # -- the window -----------------------------------------------------------

    def window(self, seconds: float):
        """Whole rounds until ``seconds`` are up: (per-round seconds,
        window seconds, rounds whose losses were not finite,
        :class:`WindowLog` of the window)."""
        tr = self.trainer
        times: List[float] = []
        failed = 0
        with WindowLog() as wlog:
            t_open = time.perf_counter()
            while time.perf_counter() - t_open < seconds:
                r0 = tr.round
                t = time.perf_counter()
                tr.step()
                dt = time.perf_counter() - t
                k = tr.round - r0
                times += [dt / k] * k
                failed += sum(1 for h in tr.history[-k:]
                              if not all(np.isfinite(list(h.values()))))
            window_s = time.perf_counter() - t_open
        return times, window_s, failed, wlog

    def traced_window(self, seconds: float, directory: str):
        """Whole rounds under the profiler, each in a step annotation."""
        tr = self.trainer
        rounds = 0
        # no Python tracer: it doubles a host-bound round's host time
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(directory, profiler_options=opts)
        try:
            t_open = time.perf_counter()
            i = 0
            while time.perf_counter() - t_open < seconds:
                with jax.profiler.StepTraceAnnotation("round", step_num=i):
                    r0 = tr.round
                    tr.step()
                    rounds += tr.round - r0
                i += 1
        finally:
            jax.profiler.stop_trace()
        return rounds

    def close(self):
        """Drop the program's state (and the program) from the device."""
        for k in ("trainer", "program", "recorder"):
            self.__dict__.pop(k, None)


# ---------------------------------------------------------------------------
# the work one round does, against the traffic file
# ---------------------------------------------------------------------------


def work_check(cell: Cell, fed: Fed) -> List[str]:
    """What one round fed, against ``traffic["expect"]``. Returns the
    differences (empty when the work is the cell's)."""
    exp = cell.traffic["expect"]
    key = cell.family.INPUT
    bad = []
    if fed.slots != exp["slots"]:
        bad.append(f"{fed.slots} client slots, expected {exp['slots']}")
    seen: Dict[bytes, str] = {}
    for r, b in enumerate(fed.rounds):
        T, m = b["labels"].shape[:2]
        if m != exp["participants"]:
            bad.append(f"round {r}: {m} participants, expected "
                       f"{exp['participants']}")
        if T != exp["local_iters"]:
            bad.append(f"round {r}: {T} local steps, expected "
                       f"{exp['local_iters']}")
        for t in range(T):
            rows = int(np.count_nonzero(b["weights"][t] > 0))
            if rows != exp["rows_per_step"]:
                bad.append(f"round {r} step {t}: {rows} "
                           f"{exp['rows_unit']}, expected "
                           f"{exp['rows_per_step']}")
            digest = hashlib.sha1(np.ascontiguousarray(b[key][t])).digest()
            if digest in seen:
                bad.append(f"round {r} step {t} repeats {seen[digest]}")
            seen.setdefault(digest, f"round {r} step {t}")
    return bad


# ---------------------------------------------------------------------------
# the reference, and the numbers compared
# ---------------------------------------------------------------------------


def reference_readings(cell: Cell, seed: int, fed: Fed, q=None,
                       transform=None) -> Readings:
    """The plain reference over the same rounds from the same seed.
    ``q`` rounds matmul operands (the control); ``transform`` alters
    each round's feed (a planted fault)."""
    fam, cfg = cell.family, cell.config
    ref = cell.config["reference"]
    canon = init_weights(fam, Frozen(cfg), weights_key(seed))
    losses, first = [], None
    for r, b in enumerate(fed.rounds):
        b = transform(b) if transform else b
        canon, (ls, lk) = scala_ref.reference_round(
            fam, cfg, canon, jax.device_put(b), tau=ref["tau"],
            eps=ref["prior_eps"], lr=cell.traffic["lr"],
            q=q or scala_ref.identity)
        losses.append([float(ls), float(lk)])
        if r == fed.per_call - 1:
            first = change_norms(fam, cfg, seed, canon)
            first_w = jax.device_get(canon)
    change = change_norms(fam, cfg, seed, canon)
    return Readings(losses, first, change, first_w, jax.device_get(canon))


def half_batch(b):
    """Planted fault: half of each client's rows left out of the step,
    the mean taken over the rest."""
    b = dict(b)
    w = b["weights"].copy()
    rows = w.shape[2]
    w[:, :, rows // 2:] = 0
    b["weights"] = w
    return b


def stale_slots(step):
    """Planted program fault: only client slot 0 receives the aggregated
    client half; the other slots keep the weights they started the round
    with, so from the second round on their clients start stale."""

    def broken(state, batches, sizes):
        old = jax.tree.map(jnp.copy, state.inner.params["client"])
        new, metrics = step(state, batches, sizes)
        client = jax.tree.map(lambda n, o: o.at[0].set(n[0]),
                              new.inner.params["client"], old)
        inner = dataclasses.replace(
            new.inner, params=dict(new.inner.params, client=client))
        return dataclasses.replace(new, inner=inner), metrics

    return broken


def moving_leaves(ref: Readings) -> List[str]:
    """Leaves whose reference first-round change is at least ``NOUGHT``
    of the median leaf's; the others move by round-off alone."""
    med = float(np.median(list(ref.first.values())))
    return [k for k, v in ref.first.items() if v >= NOUGHT * med]


def compare(prog: Readings, ref: Readings,
            per_call: int = 1) -> Dict[str, Tuple[float, str]]:
    """Gaps between the program's readings and the reference's, with where
    each was largest. A cell's limits file names the ones it compares.

    * ``loss_gap``: a round's eq. 14 or eq. 15 loss, against the larger
      of the reference's loss and the median of its losses (a client
      loss near zero would make a plain relative gap swing);
      ``first_loss_gap``: the same over the first call's rounds only;
    * ``first_update_gap`` / ``change_gap``: a leaf's norm of the first
      call's change of the global weights / of the change after the
      check calls, against the larger of the leaf's reference norm and
      the median leaf's, over :func:`moving_leaves`: the worst leaf;
      ``first_update_median`` / ``change_median``: the median leaf;
    * ``first_update_diff`` / ``change_diff``: the norm of the
      difference of the two changes (the program's weights minus the
      reference's), over the same scale: the worst leaf;
      ``first_update_diff_median`` / ``change_diff_median``: the median
      leaf;
    * ``first_update_client_gap`` / ``change_client_gap`` and
      ``first_update_client_diff`` / ``change_client_diff``: the worst
      leaf of the client half alone (the half that eq. 10 aggregates).
    """
    lp, lr = np.asarray(prog.losses), np.asarray(ref.losses)
    if lp.shape != lr.shape:
        raise ValueError(f"loss shapes {lp.shape} vs {lr.shape}")
    rel = np.abs(lp - lr) / np.maximum(np.abs(lr), np.median(np.abs(lr)))
    out = {}
    for name, block in (("loss_gap", rel), ("first_loss_gap", rel[:per_call])):
        at = np.unravel_index(np.argmax(block), block.shape)
        out[name] = (float(block[at]),
                     f"round {at[0]} {('eq14', 'eq15')[at[1]]}")
    keep = moving_leaves(ref)
    client = [k for k in keep if k.startswith("['client']")]

    def worst(gap, keys):
        leaf = max(keys, key=gap.get)
        return gap[leaf], leaf

    for name, p, r, pw, rw in (
            ("first_update", prog.first, ref.first, prog.first_w,
             ref.first_w),
            ("change", prog.change, ref.change, prog.change_w, ref.change_w)):
        m = float(np.median([r[k] for k in keep]))
        gaps = {"gap": {k: abs(p[k] - r[k]) / max(r[k], m) for k in keep}}
        if pw is not None and rw is not None:
            d = diff_norms(pw, rw)
            gaps["diff"] = {k: d[k] / max(r[k], m) for k in keep}
        for kind, gap in gaps.items():
            out[f"{name}_{kind}"] = worst(gap, keep)
            out[f"{name}_{kind}_median".replace("_gap_", "_")] = (
                float(np.median(list(gap.values()))), "median leaf")
            if client:
                out[f"{name}_client_{kind}"] = worst(gap, client)
    return out


# ---------------------------------------------------------------------------
# device facts
# ---------------------------------------------------------------------------


def device_facts(chips: int):
    """(device dict, peak) of the accelerator; raises when there is none
    or fewer chips than the cell asks for."""
    from bench import peaks

    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise SystemExit("bench: no accelerator: JAX found only the CPU")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    peak = peaks.lookup(devs[0].device_kind)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}, peak


def counters(trainer) -> Dict[str, int]:
    """The trainer's public ``int`` attributes (``round``, ``upload_bytes``
    and whatever counter the ``Trainer`` keeps besides)."""
    return {k: v for k, v in vars(trainer).items()
            if not k.startswith("_") and type(v) is int}


def memory_peak_bytes() -> Optional[int]:
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use")
              for d in jax.devices()]
    peaks_ = [p for p in peaks_ if p is not None]
    return max(peaks_) if peaks_ else None


class WindowLog:
    """What ran in a stretch of the run, as a context manager: garbage
    collections per generation and their seconds (``gc.callbacks``), and
    JAX traces, compiles and compile-cache loads with their seconds
    (``jax.monitoring``). In the window none of them should."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/core/compile/backend_compile_duration": "compiles",
              "/jax/compilation_cache/cache_retrieval_time_sec":
                  "cache_loads"}

    def __init__(self):
        self.gc = [0, 0, 0]
        self.gc_seconds = 0.0
        self.jax = {v: [0, 0.0] for v in self.EVENTS.values()}
        self._t = None

    def _gc(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.gc_seconds += time.perf_counter() - self._t
            self.gc[info["generation"]] += 1
            self._t = None

    def _jax(self, event, seconds, **_):
        name = self.EVENTS.get(event)
        if name is not None:
            self.jax[name][0] += 1
            self.jax[name][1] += seconds

    def __enter__(self):
        gc.callbacks.append(self._gc)
        jax.monitoring.register_event_duration_secs_listener(self._jax)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._gc)
        jax.monitoring.unregister_event_duration_listener(self._jax)

    def __str__(self):
        return (f"gc {self.gc} collections (gen 0, 1, 2), "
                f"{self.gc_seconds:.6f} s; " + ", ".join(
                    f"{k} {n} ({sec:.6f} s)"
                    for k, (n, sec) in self.jax.items()))


def slowest(times: List[float], n: int = 5) -> List[Tuple[int, float]]:
    """The ``n`` slowest rounds as (index in the window, seconds)."""
    order = sorted(range(len(times)), key=lambda i: -times[i])[:n]
    return [(i, times[i]) for i in sorted(order)]


def p90(times: List[float]) -> float:
    return statistics.quantiles(times, n=10, method="inclusive")[-1]

