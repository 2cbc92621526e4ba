"""Asynchronous split-federated execution on top of the split-step engine.

The synchronous round (:func:`repro.core.engine.make_round_runner`) is a
barrier: every participating client runs T local iterations from the same
aggregated model, then the FL phase averages. Real fleets are
asynchronous — clients finish at different times and their updates were
computed against *older* server params. GAS (arXiv:2409.01251) shows the
workable recipe is staleness-aware delayed aggregation; this module
implements it as a *jit-compatible event schedule*:

1. Every client holds a **snapshot** of the global client half (the
   params it trains from) tagged with the server **version** it was taken
   at, plus a sampled **finish time** (:mod:`repro.fed.delays`).
2. One call of the async runner is one **event**: the ``cohort`` earliest
   finishers arrive. Their T local iterations run on a dense sparse-slot
   axis (gathered from the static K slots, exactly the engine's
   ``slot_gather`` path), with label priors and logit adjustments
   recomputed over the *arrival cohort* — the same per-subset semantics
   the sync path applies per participating subset.
3. The arrivals' trained client halves are folded into the global model
   with **staleness-weighted delayed aggregation** (FedAsync/GAS-style
   model mixing): per-arrival weights are the aggregator's weights
   decayed by ``staleness_decay ** age`` (age = server versions elapsed
   since the snapshot), renormalized over the cohort, and the global
   client half moves ``mix_rate`` of the way to the cohort average. The
   server half trains in-scan as always (it is never averaged) with an
   optional FedOpt ``server_optimizer`` over its event delta.
4. The cohort re-snapshots the new global model at the new version,
   samples fresh delays, and the event clock advances to the cohort's
   latest arrival. Busy clients keep their snapshots and finish times.

Everything — cohort selection, gather/scatter, delay sampling, the
staleness weights — is pure jax inside one compiled program per event.

**The sync round is the zero-delay special case**: with
``delays=constant(0)`` and ``cohort=K`` every client arrives at every
event with staleness 0, the cohort average is the full FedAvg, and
``mix_rate=1`` replaces the global model with it — bit-for-bit the
synchronous round runner (test-enforced at fp32 tolerance in
``tests/test_async.py``).

Snapshot storage (``snapshots=``):

* ``"dense"`` — the legacy layout: ``client_params`` materializes one
  client-half snapshot *per slot*, O(K x |w_c|) memory.
* ``"delta"`` — the million-client layout. The state invariant below
  says ``client_params[k]`` IS the global client half as of
  ``version[k]``: the per-client delta against the tagged server
  version is **identically zero**, so nothing per-client needs storing.
  A fixed-size **ring** of the ``ring_size`` most recent global client
  halves (slot ``v % ring_size`` holds global@v) plus the existing
  (K,) ``version`` tags reconstruct any snapshot on gather:
  ``ring[max(version_k, server_version - ring_size + 1) % ring_size]``.
  Resident snapshot memory is O(ring_size x |w_c| + cohort) — flat in
  K — and the path is **bit-identical** to dense storage while every
  arrival's staleness is < ``ring_size`` (test-enforced). A snapshot
  whose base version aged out of the ring is clamped to the oldest
  retained version — bounded-staleness eviction: the straggler trains
  from a slightly newer global model than it was dispatched with,
  which only *reduces* its effective staleness. Per-client optimizer
  state is not stored on device either, so ``"delta"`` requires a
  stateless local optimizer (plain SGD — the paper's setting),
  ``opt_state_policy="reset"``, or the **host-paged moment store**
  (``paged_opt=True`` + :class:`HostOptPager`): the cold (K, ...)
  moment stack lives in host memory and only the arrival cohort's rows
  page to the device per event.

The arrival pop itself has three implementations (:data:`ARRIVALS`,
``arrival=``): the legacy O(K log K) lexsort, an O(K)-work composite-key
``lax.top_k`` pop (bit-identical, including ties), and a client-mesh-
sharded pop (per-shard top-k + O(cohort x shards) merge) that keeps the
(K,) ``version``/``finish_time`` scalars sharded — at K=1e6 the lexsort
IS the event cost, see ``benchmarks/BENCH_scale.json``.

:class:`AsyncFedState` invariants (maintained by :func:`init_async_state`
and every runner call; rely on them, don't re-derive):

* ``version[k] <= server_version`` elementwise; ``server_version``
  increments by exactly 1 per event.
* ``client_params[k]`` is the global client half as of ``version[k]`` —
  slots with ``version[k] == server_version`` hold the *current* global
  model. (``snapshots="delta"`` stores this redundancy-free: the ring
  holds one entry per recent version instead of one per client.)
* ``finish_time[k] >= now`` for busy clients; arrivals satisfy
  ``finish_time[k] <= new now`` at the event that pops them and are
  re-armed strictly into the future (for nonzero delays).
* ``server_version - version`` is the per-client staleness age — under a
  full-barrier schedule it reproduces the sync
  :func:`repro.fed.aggregators.staleness_weighted` age bookkeeping.

The manual-SPMD backend (``backend="lace_dp"``, pass ``mesh`` and
``batch_specs``) runs the whole event inside one ``shard_map``: each
shard of the client mesh axes pops ``cohort / n_shards`` of *its own*
earliest finishers (a balanced two-tier schedule — the shard is the
"edge", the psum across shards is the server fold), gathers them from
its local slots (or the replicated ring), and the cohort-weight
normalization / cohort average / event clock are combined with psums.
The per-shard pop is the one scheduling difference vs the single-program
runner: arrivals are balanced per shard rather than popped globally
(with zero delays and ``cohort=K`` the two schedules coincide).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ScalaConfig
from repro.core import engine
from repro.core.split import (normalize_client_weights, stack_client_params,
                              weighted_mean)
from repro.fed import aggregators as _agg
from repro.fed.delays import DelayModel
from repro.optim import optimizers, schedules
from repro.perf import trace

#: snapshot storage layouts for :class:`AsyncFedState`.
SNAPSHOT_MODES = ("dense", "delta")

#: arrival-pop implementations for the event schedule (see
#: :func:`arrival_cohort` / :func:`sharded_arrival_cohort`): ``"sort"``
#: is the legacy O(K log K) lexsort, ``"topk"`` the O(K)-work composite
#: -key ``lax.top_k`` pop (bit-identical), ``"topk:sharded"`` the
#: client-mesh-sharded pop (per-shard local top-k + one O(cohort x
#: shards) merge, bit-identical to the single-device pop).
ARRIVALS = ("sort", "topk", "topk:sharded")

#: per-arrival lr scaling policies (see :func:`make_async_runner`).
LR_SCALES = ("none", "cohort")

#: ring_versions tag for a slot that has never been written.
_NO_VERSION = jnp.int32(-(2 ** 30))


@dataclass(frozen=True)
class AsyncFedState:
    """Per-client dispatch state threaded through async events.

    client_params: (K, ...) stacked per-client snapshots of the global
    client half (what each client is training from) — ``()`` under
    ``snapshots="delta"``, where the ring replaces it;
    version: (K,) int32 server version each snapshot was taken at;
    server_version: () int32 global version (events applied so far);
    finish_time: (K,) float32 simulated completion time per client;
    now: () float32 event clock (the last cohort's latest arrival);
    key: PRNG key driving delay sampling;
    agg_state: aggregator carry (e.g. staleness ages) — usually () since
    the runtime tracks ages itself via ``version``;
    server_opt: server-side FedOpt optimizer state (or ());
    ring: (ring_size, ...) recent global client halves, slot
    ``v % ring_size`` holding global@v (``snapshots="delta"`` only);
    ring_versions: (ring_size,) int32 version tag per ring slot
    (un-written slots carry a large negative sentinel);
    retries: (K,) int32 consecutive deadline misses per client (drives
    the exponential re-dispatch backoff; ``()`` on legacy states);
    guard: running-median state for guarded aggregation's norm clip
    (:func:`repro.fed.guards.init_state`, or ``()``).
    """

    client_params: Any
    version: Any
    server_version: Any
    finish_time: Any
    now: Any
    key: Any
    agg_state: Any = ()
    server_opt: Any = ()
    ring: Any = ()
    ring_versions: Any = ()
    retries: Any = ()
    guard: Any = ()


jax.tree_util.register_dataclass(
    AsyncFedState,
    data_fields=("client_params", "version", "server_version", "finish_time",
                 "now", "key", "agg_state", "server_opt", "ring",
                 "ring_versions", "retries", "guard"),
    meta_fields=())


def init_async_state(key, client_params, delays: DelayModel, *,
                     aggregator=None,
                     server_optimizer: Optional[optimizers.Optimizer] = None,
                     server_params=None,
                     snapshots: str = "dense",
                     ring_size: int = 64,
                     num_clients: Optional[int] = None,
                     mesh=None, guards=None) -> AsyncFedState:
    """Dispatch all K clients at version 0.

    ``client_params`` is the stacked client half (every slot holds the
    same init — :func:`repro.core.split.stack_client_params`); each
    client's first completion delay is sampled immediately, so the first
    event pops the cohort of earliest finishers. Pass the same
    ``aggregator`` / ``server_optimizer`` the runner was built with so
    their state is initialized to matching shapes.

    With ``snapshots="delta"`` the per-client snapshots are NOT
    materialized: pass the global client half stacked over a single slot
    (or any stacked layout — row 0 is taken) plus ``num_clients=K``, and
    the state carries a ``ring_size``-deep ring of recent global client
    halves instead — O(ring_size), not O(K). ``ring_size`` bounds the
    reconstructable staleness (see the module docstring's eviction
    semantics).

    With ``mesh=`` the (K,) schedule scalars — ``version`` and
    ``finish_time`` — are laid out sharded over the mesh's client axes
    (:func:`repro.sharding.logical.client_scalar_spec`), and the initial
    delay sampling compiles with that output sharding
    (:meth:`repro.fed.delays.DelayModel.sample_sharded` — threefry is
    value-deterministic, so the sharded init is bit-identical to the
    unsharded one). Pair with ``make_async_runner(arrival=
    "topk:sharded", mesh=...)`` so no event materializes the (K,)
    scalars on one device.
    """
    if snapshots not in SNAPSHOT_MODES:
        raise ValueError(f"unknown snapshots mode {snapshots!r}; expected "
                         f"{SNAPSHOT_MODES}")
    lead = jax.tree.leaves(client_params)[0].shape[0]
    K = lead if num_clients is None else num_clients
    if snapshots == "dense" and num_clients is not None and lead != K:
        raise ValueError(f"dense snapshots need client_params stacked over "
                         f"all {K} clients, got {lead} slots")
    k_delay, k_carry = jax.random.split(jnp.asarray(key))
    if server_optimizer is not None and server_params is None:
        raise ValueError("init_async_state needs server_params when a "
                         "server_optimizer is given")
    if snapshots == "delta":
        if ring_size < 1:
            raise ValueError(f"ring_size must be >= 1, got {ring_size}")
        global_c = jax.tree.map(lambda a: a[0], client_params)
        snap = ()
        ring = jax.tree.map(
            lambda g: jnp.broadcast_to(g[None], (ring_size,) + g.shape),
            global_c)
        ring_versions = jnp.full((ring_size,), _NO_VERSION,
                                 jnp.int32).at[0].set(0)
    else:
        snap, ring, ring_versions = client_params, (), ()
    version = jnp.zeros((K,), jnp.int32)
    retries = jnp.zeros((K,), jnp.int32)
    finish_time = delays.sample(k_delay, (K,)).astype(jnp.float32)
    if mesh is not None:
        from jax.sharding import NamedSharding

        from repro.sharding.logical import client_scalar_spec

        spec = client_scalar_spec(mesh, K)
        version = jax.device_put(version, NamedSharding(mesh, spec))
        retries = jax.device_put(retries, NamedSharding(mesh, spec))
        finish_time = delays.sample_sharded(k_delay, K, mesh)
    guard = ()
    if guards is not None:
        from repro.fed import guards as _guards_mod

        gp = _guards_mod.make_guards(guards)
        guard = _guards_mod.init_state() if gp.stateful else ()
    return AsyncFedState(
        client_params=snap,
        version=version,
        server_version=jnp.zeros((), jnp.int32),
        finish_time=finish_time,
        now=jnp.zeros((), jnp.float32),
        key=k_carry,
        agg_state=aggregator.init(K) if aggregator is not None else (),
        server_opt=(server_optimizer.init(server_params)
                    if server_optimizer is not None else ()),
        ring=ring,
        ring_versions=ring_versions,
        retries=retries,
        guard=guard)


def _pop_topk(finish_time, version, cohort: int):
    """O(K)-work selection of the ``cohort`` minima under the composite
    lexicographic key (finish_time, version, slot).

    The composite key never materializes as one word — no available
    dtype holds an exact (f32, i32, i32) pack — so the selection runs as
    a short ladder of **float32** ``lax.top_k`` stages, one per key
    component, each refining the boundary tie set of the previous one:

    1. ``finish_time`` (native f32): one top-k gives the boundary value
       ``b`` (the cohort-th earliest finish); everything strictly
       earlier is selected, the ties at ``b`` continue.
    2. ``version`` split into its 16-bit halves (``v >> 16`` and
       ``v & 0xffff`` — two's-complement floor decomposition, each half
       exactly representable in f32, lexicographically monotone in
       ``v``): two more masked top-k passes over the tie set.
    3. slot id: ``lax.top_k`` breaks equal values by *lower index
       first*, so one final top-k over the residual tie mask pops the
       remaining slots in ascending id order.

    Every stage is O(K) work / O(log K) depth and stays on XLA's fast
    f32 TopK path — int32 ``top_k`` would do stage 2 in one pass but
    lowers to a full O(K log K) sort on CPU, which is the cost this
    function exists to remove. Bit-identical to the lexsort pop
    (test-enforced in ``tests/test_arrival.py``), including the FIFO
    tie-break that prevents slot starvation.
    """
    K = finish_time.shape[0]
    stages = [finish_time]
    if version is not None:
        v = version.astype(jnp.int32)
        stages += [(v >> 16).astype(jnp.float32),
                   (v & 0xFFFF).astype(jnp.float32)]
    selected = jnp.zeros((K,), jnp.bool_)
    eligible = jnp.ones((K,), jnp.bool_)
    need = jnp.int32(cohort)            # stays >= 1: strictly-below-the-
    for k in stages:                    # boundary counts are < need
        kk = jnp.where(eligible, k.astype(jnp.float32), jnp.inf)
        # the barrier keeps XLA from constant-folding a static slice of
        # the top_k output into its sort-based rewrite (a full O(K log K)
        # sort on CPU — the exact cost this pop exists to remove); with
        # it the fast O(K) TopK custom call survives even on the first
        # stage, where `need` is still the trace-time constant `cohort`
        vals = jax.lax.optimization_barrier(jax.lax.top_k(-kk, cohort)[0])
        b = -jnp.take(vals, need - 1)   # need-th smallest eligible key
        strict = eligible & (kk < b)
        selected |= strict
        need -= strict.sum(dtype=jnp.int32)
        eligible &= kk == b
    # the residual ties differ only in slot id: top_k's lower-index-
    # first rule pops the `need` lowest ids (the lexsort's stability)
    tvals, tidx = jax.lax.top_k(eligible.astype(jnp.float32), cohort)
    take = (jnp.arange(cohort, dtype=jnp.int32) < need) & (tvals > 0)
    selected |= jnp.zeros((K,), jnp.bool_).at[tidx].set(take, mode="drop")
    # ascending idx: all selected values are equal, ties -> index order
    _, idx = jax.lax.top_k(selected.astype(jnp.float32), cohort)
    mask = selected.astype(jnp.float32)
    t_event = jnp.max(jnp.take(finish_time, idx))
    return idx, mask, t_event


def arrival_cohort(finish_time, cohort: int, version=None,
                   method: str = "sort"):
    """The event schedule's pop: the ``cohort`` earliest finishers.

    Returns (idx (cohort,) ascending slot ids, mask (K,) 0/1 float32,
    t_event — the cohort's latest finish time, i.e. the new clock).
    Ties (equal finish times) break by snapshot ``version`` — the
    longest-waiting client goes first (FIFO) — then by slot id (lexsort
    is stable). Without the version key, degenerate schedules (zero or
    constant-tied delays with ``cohort < K``) would re-arm the lowest
    slot ids at the same finish time and starve every other slot; with
    it, zero delays pop slots round-robin in blocks of ``cohort``.

    ``method`` picks the implementation (:data:`ARRIVALS`): ``"sort"``
    is the O(K log K) lexsort, ``"topk"`` the O(K)-work composite-key
    :func:`_pop_topk` — **bit-identical** outputs, including every tie
    case (test-enforced). The mesh-sharded pop is
    :func:`sharded_arrival_cohort`.
    """
    if method == "topk":
        return _pop_topk(finish_time, version, cohort)
    if method != "sort":
        raise ValueError(f"unknown arrival method {method!r}; expected "
                         "'sort' or 'topk' (use sharded_arrival_cohort "
                         "for 'topk:sharded')")
    if version is None:
        order = jnp.argsort(finish_time)
    else:
        order = jnp.lexsort((version, finish_time))
    idx = jnp.sort(order[:cohort])
    K = finish_time.shape[0]
    mask = jnp.zeros((K,), jnp.float32).at[idx].set(1.0)
    t_event = jnp.max(jnp.take(finish_time, idx))
    return idx, mask, t_event


def sharded_arrival_cohort(finish_time, cohort: int, version, *, mesh):
    """The pop with the (K,) schedule scalars sharded over the client
    mesh axes: per-shard local top-``cohort`` candidates + one
    O(cohort x shards) merge. Bit-identical to the single-device pop.

    Each shard runs :func:`_pop_topk` on its local (K/S,) slice under
    the SAME composite (finish_time, version, slot) order — the global
    top-``cohort`` is contained in the union of per-shard top-cohorts,
    because any globally selected slot has fewer than ``cohort``
    predecessors globally, hence fewer within its own shard. The
    all-gathered ``S x min(cohort, K/S)`` candidate triples are merged
    with one small lexsort (slot id as the final key makes the merge
    deterministic and exact). No step materializes a (K,) array on one
    device: the inputs stay sharded, the merge is O(cohort x shards),
    and the returned ``mask`` is sharded like the inputs.

    Returns (idx (cohort,) global slot ids ascending — replicated,
    mask (K,) float32 sharded over the client axes, t_event —
    replicated).
    """
    from jax.sharding import PartitionSpec as P

    axes = engine.mesh_axes(mesh)
    n_shards = engine.client_shard_count(mesh)
    K = finish_time.shape[0]
    if K % n_shards:
        raise ValueError(f"{K} client slots must divide over the "
                         f"{n_shards} client shards for the sharded pop")
    K_l = K // n_shards
    c_l = min(cohort, K_l)
    cspec = P(axes.client or None)

    def body(ft_l, v_l):
        li, _, _ = _pop_topk(ft_l, v_l, c_l)
        shard_ix = jnp.int32(0)
        for a in axes.client:
            shard_ix = shard_ix * dict(mesh.shape)[a] + jax.lax.axis_index(a)
        cand = (jnp.take(ft_l, li), jnp.take(v_l, li), li + shard_ix * K_l)
        if axes.client:
            cand = tuple(jax.lax.all_gather(c, axes.client, tiled=True)
                         for c in cand)
        ft_c, v_c, g_c = cand
        # O(cohort x shards) merge under the composite order; global
        # slot ids are distinct so the order is total and exact
        order = jnp.lexsort((g_c, v_c, ft_c))[:cohort]
        idx = jnp.sort(jnp.take(g_c, order))
        t_event = jnp.max(jnp.take(ft_c, order))
        loc = idx - shard_ix * K_l
        loc = jnp.where((loc >= 0) & (loc < K_l), loc, K_l)
        mask_l = jnp.zeros((K_l,), jnp.float32).at[loc].set(1.0, mode="drop")
        return idx, mask_l, t_event

    fn = jax.shard_map(body, mesh=mesh, in_specs=(cspec, cspec),
                       out_specs=(P(), cspec, P()), check_vma=False)
    return fn(finish_time, version)


def make_arrival_pop(cohort: int, arrival: str = "sort", *, mesh=None):
    """The configured pop as one function ``pop(finish_time, version) ->
    (idx, mask, t_event)`` (:data:`ARRIVALS` vocabulary).

    The async runner builds its in-event pop through this, and the
    host-paged optimizer path (:class:`HostOptPager`) uses the SAME
    constructor for its pre-event idx prediction — the two pops are the
    same deterministic function of the same state, so the host gather
    always addresses the slots the event actually pops.
    """
    if arrival not in ARRIVALS:
        raise ValueError(f"unknown arrival {arrival!r}; expected {ARRIVALS}")
    if arrival == "topk:sharded":
        if mesh is None:
            raise ValueError("arrival='topk:sharded' needs mesh= (the "
                             "client axes the schedule scalars shard over)")
        return lambda ft, v: sharded_arrival_cohort(ft, cohort, v, mesh=mesh)
    return lambda ft, v: arrival_cohort(ft, cohort, v, method=arrival)


def ring_lookup(ring, versions, server_version, ring_size: int):
    """Reconstruct dense snapshots for slots ``versions`` from the ring.

    ``versions`` (m,) int32 snapshot tags; returns (snapshots with a
    leading (m,) axis, effective versions (m,)). A version older than
    the ring depth is clamped to the oldest retained version
    ``server_version - ring_size + 1`` (bounded-staleness eviction);
    otherwise the lookup is exact — ring slot ``v % ring_size`` holds
    the global client half written at version ``v``, and any v within
    the last ``ring_size`` versions is the slot's latest write.
    """
    eff = jnp.maximum(versions,
                      server_version - jnp.int32(ring_size - 1))
    slot = eff % ring_size
    return jax.tree.map(lambda r: jnp.take(r, slot, axis=0), ring), eff


def async_state_bytes(afed: AsyncFedState) -> dict:
    """Resident-memory accounting of an :class:`AsyncFedState`.

    ``snapshot_bytes`` is the param-sized component — O(K x |w_c|) for
    dense snapshots, O(ring_size x |w_c|) for the delta ring — and
    ``per_client_scalar_bytes`` the unavoidable (K,) tags (version +
    finish_time, ~8 bytes/client). The O(cohort + ring) scaling claim
    (BENCH_scale.json) is about the param-sized component.
    """

    def nbytes(tree) -> int:
        return int(sum(int(np.prod(l.shape)) * l.dtype.itemsize
                       for l in jax.tree.leaves(tree)))

    snap = nbytes(afed.client_params) + nbytes(afed.ring)
    per_client = nbytes(afed.version) + nbytes(afed.finish_time)
    other = nbytes((afed.ring_versions, afed.server_version, afed.now,
                    afed.key, afed.agg_state, afed.server_opt,
                    afed.retries, afed.guard))
    return {"snapshot_bytes": snap,
            "per_client_scalar_bytes": per_client,
            "other_bytes": other,
            "total_bytes": snap + per_client + other}


class HostOptPager:
    """Host-paged per-client optimizer moments for ``opt_state_policy=
    "carry"`` at large K.

    ``snapshots="delta"`` keeps the param-sized async state O(cohort +
    ring) but stores no per-client optimizer state, which restricted it
    to stateless sgd or ``opt_state_policy="reset"``. The pager lifts
    that restriction without re-growing device memory: the cold (K, ...)
    moment stack lives in **host memory** (numpy buffers, paged to the
    device on demand), and each event gathers only the arrival cohort's
    ``(cohort, ...)`` rows to the device, feeds them through the local
    scan as the cohort's carried moments, and scatters the updated rows
    back. Device-resident optimizer state stays O(cohort); host state is
    O(K x |moments|) where it is cheap.

    Choreography (what :func:`repro.api.build` wires up for
    ``ExecutionSpec.opt_paging="host"``):

    1. ``pop = make_arrival_pop(cohort, arrival, ...)`` predicts the
       event's arrival ``idx`` from ``afed`` — the same deterministic
       function the event program applies internally, so the prediction
       is exact.
    2. ``cohort_opt = pager.gather(idx)`` pages the cohort's moments in.
    3. the paged event (``make_async_runner(paged_opt=True)``) consumes
       ``cohort_opt`` and returns the post-scan moments as a fourth
       output.
    4. ``pager.scatter(idx, new_cohort_opt)`` pages them back out.

    One pager backs one live training state (it is mutable host
    memory); call :meth:`reset` when re-initializing the state.
    """

    def __init__(self, opt: optimizers.Optimizer, client_template,
                 num_clients: int):
        """``client_template`` is ONE client's (unstacked) client half;
        the store is ``num_clients`` stacked rows of
        ``opt.init(client_template)``'s shapes (zero-initialized,
        exactly ``vmap(opt.init)`` over identical snapshots)."""
        proto = jax.eval_shape(opt.init, client_template)
        self.num_clients = num_clients
        self._store = jax.tree.map(
            lambda s: np.zeros((num_clients,) + tuple(s.shape), s.dtype),
            proto)

    def reset(self):
        """Zero every moment row (a fresh ``opt.init`` for all K)."""
        jax.tree.map(lambda a: a.fill(0), self._store)

    def gather(self, idx):
        """Page rows ``idx`` in: host (K, ...) -> device (cohort, ...)."""
        idx = np.asarray(idx)
        return jax.tree.map(lambda a: jnp.asarray(a[idx]), self._store)

    def scatter(self, idx, cohort_opt):
        """Page the cohort's updated moments back out to rows ``idx``."""
        idx = np.asarray(idx)

        def put(a, s):
            a[idx] = np.asarray(s).astype(a.dtype, copy=False)
            return a

        jax.tree.map(put, self._store, cohort_opt)

    def nbytes(self) -> int:
        """Host-resident bytes of the cold moment stack."""
        return int(sum(a.nbytes for a in jax.tree.leaves(self._store)))


def _resolve_schedule(schedule, scala: ScalaConfig, lr_scale: str,
                      cohort: int, num_clients: Optional[int]):
    """The event schedule's lr policy (``lr_scale``).

    The global ``step`` counter advances once per *local iteration* of
    whichever cohort arrived — with ``cohort < K`` the schedule ticks
    K/cohort times faster per unit of fleet-wide work than the sync
    round's, and each event moves the global model a full ``mix_rate``
    step from a cohort-sized sample. ``"cohort"`` scales the lr by
    ``cohort / K`` so per-event aggregate movement matches the sync
    round's per-participant scale; at ``cohort == K`` the factor is
    exactly 1.0 and the schedule is bit-identical to ``"none"``
    (test-enforced sync-equivalence).
    """
    if lr_scale not in LR_SCALES:
        raise ValueError(f"unknown lr_scale {lr_scale!r}; expected "
                         f"{LR_SCALES}")
    sched = schedule if schedule is not None else schedules.constant(scala.lr)
    if lr_scale == "none":
        return sched
    if num_clients is None:
        raise ValueError("lr_scale='cohort' needs num_clients= (the factor "
                         "is cohort / K)")
    factor = jnp.float32(cohort / num_clients)
    base = sched
    return lambda step: base(step) * factor


def make_async_runner(model: engine.SplitModel, scala: ScalaConfig, *,
                      delays: DelayModel,
                      cohort: int,
                      backend: str = "logits",
                      boundary: str = "fused",
                      optimizer: Optional[optimizers.Optimizer] = None,
                      schedule: Optional[Callable] = None,
                      ce_chunk: Optional[int] = None,
                      staleness_decay: float = 0.5,
                      mix_rate: float = 1.0,
                      aggregator=None,
                      server_optimizer: Optional[optimizers.Optimizer] = None,
                      server_lr: float = 1.0,
                      opt_state_policy: str = "carry",
                      unroll=1,
                      precision: str = "f32",
                      snapshots: str = "dense",
                      ring_size: int = 64,
                      lr_scale: str = "none",
                      num_clients: Optional[int] = None,
                      emit_client_metrics: bool = True,
                      arrival: str = "sort",
                      paged_opt: bool = False,
                      mesh=None, batch_specs=None,
                      deadline: Optional[float] = None,
                      backoff: float = 2.0,
                      faults=None, guards=None):
    """Build the async event program: ``async_fn(state, afed,
    round_batches, data_sizes=None) -> (state, afed, metrics)``.

    ``round_batches`` leaves are (T, K, Bk, ...) — one local-iteration
    schedule for every static slot; only the arrival cohort's columns are
    computed (sparse-slot gather), so the per-event cost is
    ~``cohort / K`` of a full sync round. Alternatively the leaves may be
    (T, cohort, Bk, ...) — *cohort-sized* batches consumed by the
    arrivals directly, skipping the O(K) batch materialization entirely
    (the million-client path; requires a prior-free aggregator since the
    (K,)-indexed aggregation priors cannot be derived from them).

    * ``delays`` / ``cohort`` — the event schedule: completion delays per
      dispatch, and how many arrivals each event waits for
      (``cohort=K`` is a full barrier; ``cohort=1`` is fully async).
    * ``staleness_decay`` / ``mix_rate`` — delayed-aggregation knobs: an
      arrival whose snapshot is ``a`` versions old is decayed by
      ``staleness_decay ** a`` inside the cohort weights, and the global
      client half moves ``mix_rate`` toward the cohort average
      (FedAsync-style mixing; ``mix_rate=1`` replaces it).
    * ``aggregator`` — base per-arrival weights before the staleness
      decay (default: data-size :func:`repro.fed.aggregators.weighted`,
      matching the sync runner's default). Stateful aggregators thread
      their carry through ``afed.agg_state``; note the runtime already
      tracks ages via ``version``, so :func:`staleness_weighted` here
      would double-decay.
    * ``server_optimizer`` / ``server_lr`` — optional FedOpt on the
      server half's event delta (state in ``afed.server_opt``), the same
      semantics as the sync runner's.
    * ``opt_state_policy`` — the cohort's client optimizer state at the
      event boundary: ``carry`` scatters the cohort's updated moments
      back to their slots (busy clients' moments are untouched),
      ``reset`` zeroes the cohort's, ``average`` redistributes the
      cohort-weighted mean over the cohort slots.
    * ``precision`` — the engine step's compute policy
      (:data:`repro.core.engine.PRECISIONS`): ``"bf16"`` runs the
      cohort's local forward/backward in bfloat16 against f32 master
      params; the staleness weights, priors, and delayed aggregation
      stay f32.
    * ``snapshots`` / ``ring_size`` — the :class:`AsyncFedState`
      storage layout (module docstring): ``"delta"`` replaces the
      (K, ...) per-client snapshots with a ``ring_size``-deep ring of
      recent global client halves, bit-identical to ``"dense"`` while
      staleness stays below ``ring_size`` and O(cohort + ring) resident
      otherwise. Requires a stateless optimizer or
      ``opt_state_policy="reset"`` (no per-client moments are stored)
      and builds ``state.params["client"]`` over ONE slot.
    * ``lr_scale`` — per-arrival lr scaling (:data:`LR_SCALES`):
      ``"cohort"`` multiplies the schedule by ``cohort / num_clients``
      (pass ``num_clients=``); ``"none"`` is the historical behavior.
    * ``emit_client_metrics`` — include the (K,) ``arrival_mask`` /
      ``staleness`` vectors in the metrics (default). Disable at large K
      so the per-event host transfer stays O(cohort).
    * ``arrival`` — the pop implementation (:data:`ARRIVALS`):
      ``"sort"`` the legacy O(K log K) lexsort, ``"topk"`` the O(K)-work
      composite-key ``lax.top_k`` pop (bit-identical, the large-K
      default-to-be), ``"topk:sharded"`` the client-mesh-sharded pop —
      pass ``mesh=`` (its client axes; works with any backend) and
      initialize with ``init_async_state(mesh=...)`` so the (K,)
      schedule scalars never land on one device. Under
      ``backend="lace_dp"`` the pop is already per-shard; ``"sort"`` /
      ``"topk"`` pick the local method there and ``"topk:sharded"`` is
      rejected.
    * ``paged_opt`` — host-paged per-client optimizer moments
      (:class:`HostOptPager`; requires ``snapshots="delta"`` and
      ``opt_state_policy="carry"``). The event takes an extra
      ``cohort_opt`` argument (the cohort's paged-in moments, replacing
      the fresh ``opt.init`` delta snapshots otherwise use) and returns
      the post-scan moments as a FOURTH output for the pager to write
      back — this is what lifts delta's stateless/reset restriction.
    * ``mesh`` / ``batch_specs`` — required iff ``backend="lace_dp"``:
      the whole event runs inside one ``shard_map`` with the client axis
      sharded over the mesh's client axes; each shard pops
      ``cohort / n_shards`` of its local finishers (balanced two-tier
      schedule, module docstring). Requires cohort and K divisible by
      the client-shard count and a shard-decomposable aggregator
      (``Aggregator.shard_local``).

    ``state.params["client"]`` always holds the *current* global client
    half broadcast over the K slots (checkpoint/eval-compatible with the
    sync runner) — over a single slot under ``snapshots="delta"``; the
    per-client training snapshots live in ``afed.client_params`` (dense)
    or ``afed.ring`` (delta).

    Metrics extend the engine's with the async observables:
    ``arrival_mask`` (K,), ``staleness`` (K,) pre-event ages (both
    gated on ``emit_client_metrics``), ``staleness_mean`` over the
    cohort, ``t_event``, and ``server_version`` post-event.

    Fault tolerance:

    * ``deadline`` / ``backoff`` — graceful degradation of the cohort
      barrier: the event fires at ``min(cohort-th finish, first finish +
      deadline)``; arrivals that miss it are excluded from the event
      (mask-folded out of the scan, so cohort priors cover only the
      present subset), keep their version/snapshot/moments, and are
      requeued at ``t_event + delay * backoff**retries`` (exponential
      backoff per consecutive miss — a stalled client stops blocking
      the schedule). ``deadline=None`` is the legacy unbounded wait.
    * ``faults`` — :class:`repro.fed.faults.FaultModel` (per-*arrival*
      here): drops leave the contribution mask, corruption poisons the
      arriving update in transit, stalls multiply the re-dispatch delay
      by ``stall_factor`` (rescued later by deadline/backoff).
    * ``guards`` — :class:`repro.fed.guards.GuardPolicy`: rejected
      arrivals trigger a ``lax.cond`` re-run of the cohort scan under
      the survivor mask (priors recomputed as if they never arrived)
      and are zeroed out of the delayed aggregation; they re-dispatch
      fresh from the new global. Bit-identical to the unguarded event
      when nothing is rejected. ``clip:TAU`` needs ``afed.guard``
      (``init_async_state(..., guards=...)``).
    """
    if opt_state_policy not in engine.OPT_STATE_POLICIES:
        raise ValueError(f"unknown opt_state_policy {opt_state_policy!r}; "
                         f"expected {engine.OPT_STATE_POLICIES}")
    if snapshots not in SNAPSHOT_MODES:
        raise ValueError(f"unknown snapshots mode {snapshots!r}; expected "
                         f"{SNAPSHOT_MODES}")
    if snapshots == "delta" and opt_state_policy == "average":
        raise ValueError(
            "snapshots='delta' stores no per-client optimizer state to "
            "average; use opt_state_policy 'reset' (or 'carry' with a "
            "stateless optimizer)")
    if cohort < 1:
        raise ValueError(f"cohort must be >= 1, got {cohort}")
    if arrival not in ARRIVALS:
        raise ValueError(f"unknown arrival {arrival!r}; expected {ARRIVALS}")
    if paged_opt and (snapshots != "delta" or opt_state_policy != "carry"):
        raise ValueError(
            "paged_opt pages per-client moments for snapshots='delta' + "
            "opt_state_policy='carry' (dense snapshots already store them "
            f"on device); got snapshots={snapshots!r}, "
            f"opt_state_policy={opt_state_policy!r}")
    from repro.fed import faults as _faults
    from repro.fed import guards as _guards

    if faults is not None:
        faults = _faults.make_faults(faults)
    if guards is not None:
        guards = _guards.make_guards(guards)
    if deadline is not None and deadline <= 0:
        raise ValueError(f"deadline must be > 0, got {deadline}")
    if backoff < 1.0:
        raise ValueError(f"backoff must be >= 1, got {backoff}")
    robust = (deadline is not None) or (faults is not None) \
        or (guards is not None)
    if robust and backend == "lace_dp":
        raise ValueError(
            "deadline/faults/guards are not supported on the lace_dp event "
            "(its pop and FL phase run inside shard_map); use a single-host "
            "backend")
    if robust and paged_opt:
        raise ValueError(
            "deadline/faults/guards are not supported with host-paged "
            "optimizer moments (the pager's arrival prediction does not "
            "model partial cohorts)")
    delta = snapshots == "delta"
    opt = optimizer if optimizer is not None else optimizers.sgd()
    agg = aggregator if aggregator is not None else _agg.weighted()
    sched = _resolve_schedule(schedule, scala, lr_scale, cohort, num_clients)

    if backend == "lace_dp":
        if arrival == "topk:sharded":
            raise ValueError(
                "backend 'lace_dp' pops per shard already (the balanced "
                "two-tier schedule); arrival 'sort' or 'topk' picks its "
                "local pop method")
        if paged_opt:
            raise ValueError("paged_opt is not supported on the lace_dp "
                             "event (its delta path keeps moments local)")
        return _make_async_runner_dp(
            model, scala, boundary=boundary, delays=delays, cohort=cohort,
            opt=opt, sched=sched,
            ce_chunk=ce_chunk, staleness_decay=staleness_decay,
            mix_rate=mix_rate, agg=agg, server_optimizer=server_optimizer,
            server_lr=server_lr, opt_state_policy=opt_state_policy,
            unroll=unroll, precision=precision, delta=delta,
            ring_size=ring_size, emit_client_metrics=emit_client_metrics,
            arrival=arrival, mesh=mesh, batch_specs=batch_specs)
    pop = make_arrival_pop(cohort, arrival, mesh=mesh)

    step = engine.make_split_step(model, scala, backend=backend,
                                  boundary=boundary,
                                  optimizer=opt, schedule=sched,
                                  ce_chunk=ce_chunk, precision=precision)

    @trace.scoped("fed")
    def async_fn(state: engine.TrainState, afed: AsyncFedState,
                 round_batches, data_sizes=None, cohort_opt=None):
        K = afed.version.shape[0]
        if cohort > K:
            raise ValueError(f"cohort {cohort} exceeds the {K} client slots")
        if paged_opt and cohort_opt is None:
            raise ValueError(
                "the paged event needs cohort_opt= (the arrival cohort's "
                "paged-in moments — HostOptPager.gather over the idx "
                "make_arrival_pop predicts)")
        if delta and not paged_opt and opt_state_policy == "carry" \
                and jax.tree.leaves(state.opt_state["client"]):
            raise ValueError(
                "snapshots='delta' cannot carry per-client optimizer "
                "moments (none are stored); use a stateless optimizer "
                "(plain sgd), opt_state_policy='reset', or the host-paged "
                "moment store (paged_opt=True + HostOptPager)")

        if deadline is not None and isinstance(afed.retries, tuple):
            raise ValueError(
                "deadline needs per-client retry counters (afed.retries) — "
                "rebuild the state with init_async_state")
        if guards is not None and guards.clip > 0 \
                and isinstance(afed.guard, tuple):
            raise ValueError(
                "guard norm clipping needs afed.guard (running median) — "
                "build the state with init_async_state(..., guards=...)")

        # --- event pop: who arrives, and when ---
        idx, arrival_mask, t_event = pop(afed.finish_time, afed.version)
        present = retries_sub = None
        if deadline is not None:
            # graceful degradation of the cohort barrier: fire at
            # min(cohort-th finish, first finish + deadline); arrivals
            # past the cut are excluded from the event and backed off
            ft_sub = jnp.take(afed.finish_time, idx)
            t_event = jnp.minimum(t_event, jnp.min(ft_sub)
                                  + jnp.float32(deadline))
            present = (ft_sub <= t_event).astype(jnp.float32)
            arrival_mask = jnp.zeros((K,), jnp.float32).at[idx].set(present)
            retries_sub = jnp.take(afed.retries, idx)
        staleness = (afed.server_version - afed.version).astype(jnp.float32)

        # --- fault injection: per-arrival drop / corrupt / stall ---
        contrib = present
        corrupt_sub = stall_sub = corrupt_key = None
        key_rest = afed.key
        if faults is not None:
            k_ev, key_rest = jax.random.split(afed.key)
            k_masks, corrupt_key = jax.random.split(k_ev)
            fmasks = _faults.sample_fault_masks(faults, k_masks, cohort)
            alive = 1.0 - fmasks["drop"]
            contrib = alive if contrib is None else contrib * alive
            corrupt_sub = fmasks["corrupt"] * contrib
            stall_sub = fmasks["stall"]

        # --- sparse-slot local compute from the per-client snapshots:
        # the engine's gather, sourced from the snapshots (dense) or
        # reconstructed from the version ring (delta) ---
        if delta:
            snap_c, _ = ring_lookup(afed.ring, jnp.take(afed.version, idx),
                                    afed.server_version, ring_size)
            # carried moments: the paged-in rows when paging, else the
            # fresh init delta snapshots otherwise imply
            opt_sub = (cohort_opt if paged_opt
                       else jax.vmap(opt.init)(snap_c))
            sub = engine.TrainState(
                params={"client": snap_c, "server": state.params["server"]},
                opt_state={"client": opt_sub,
                           "server": state.opt_state["server"]},
                step=state.step)
        else:
            sub = engine._gather_clients(
                engine.TrainState(
                    params={"client": afed.client_params,
                            "server": state.params["server"]},
                    opt_state=state.opt_state, step=state.step), idx)
        b_lead = jax.tree.leaves(round_batches)[0].shape[1]
        if b_lead == K:
            sub_batches = jax.tree.map(lambda a: jnp.take(a, idx, axis=1),
                                       round_batches)
        elif b_lead == cohort:
            if agg.needs_priors:
                raise ValueError(
                    f"aggregator {agg.name!r} needs (K,)-indexed aggregation "
                    "priors, which cohort-sized round_batches cannot "
                    "provide; pass full (T, K, ...) batches")
            sub_batches = round_batches
        else:
            raise ValueError(
                f"round_batches client axis is {b_lead}; expected the {K} "
                f"static slots or the {cohort}-sized arrival cohort")
        # priors / logit adjustments recompute over the arrival cohort:
        # the gathered batch IS the cohort's concatenated batch (masked
        # down to the contributing subset under deadline/faults)
        sub0 = sub  # pre-scan cohort state: guard recompute / restores
        snap0 = sub0.params["client"]

        def run_local(mask_):
            body = (lambda s, b: step(s, b, mask_)) if mask_ is not None \
                else step
            s2, ms = jax.lax.scan(body, sub0, sub_batches, unroll=unroll)
            mets = jax.tree.map(lambda a: a[-1], ms)
            if corrupt_sub is not None:
                # the update is corrupted in transit, AFTER training
                cp = _faults.corrupt_update(faults, corrupt_key,
                                            s2.params["client"], corrupt_sub)
                s2 = engine.TrainState(
                    params={"client": cp, "server": s2.params["server"]},
                    opt_state=s2.opt_state, step=s2.step)
            return s2, mets

        sub, metrics = run_local(contrib)

        # --- guarded aggregation: screen the arriving updates ---
        accept = factor = None
        new_guard_state = afed.guard
        if guards is not None:
            base = (contrib if contrib is not None
                    else jnp.ones((cohort,), jnp.float32))
            delta_u = jax.tree.map(
                lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                sub.params["client"], snap0)
            accept, factor, g_norms, new_guard_state = _guards.screen(
                guards, delta_u, base, afed.guard)
            survivor = base * accept
            rejected = base.sum() - survivor.sum()
            # >=1 rejection: re-run the cohort scan over the survivors
            # so the priors / logit adjustments match an event the
            # rejected arrivals never joined
            sub, metrics = jax.lax.cond(
                rejected > 0, lambda _: run_local(survivor),
                lambda _: (sub, metrics), None)
            if guards.clip > 0:
                delta2 = jax.tree.map(
                    lambda a, b: (a.astype(jnp.float32)
                                  - b.astype(jnp.float32)),
                    sub.params["client"], snap0)
                _, factor, _, _ = _guards.screen(guards, delta2, survivor,
                                                 afed.guard)
            # survivor == base bitwise when nothing was rejected
            contrib = survivor

        mask_eff = arrival_mask
        if contrib is not None:
            mask_eff = jnp.zeros((K,), jnp.float32).at[idx].set(contrib)

        # --- staleness-weighted delayed aggregation (GAS / FedAsync) ---
        p_k = p_global = None
        if agg.needs_priors:
            p_k, p_global = _agg.aggregation_priors(
                model.num_classes, round_batches["labels"],
                round_batches.get("weights"), client_axis=1)
        ctx = _agg.AggContext(num_clients=K, mask=mask_eff,
                              data_sizes=data_sizes, p_k=p_k,
                              p_global=p_global)
        w_base, agg_state = agg.client_weights(ctx, afed.agg_state)
        decay = jnp.power(jnp.float32(staleness_decay), staleness)
        r_hat = normalize_client_weights(w_base * decay, mask_eff)
        pc_sub = sub.params["client"]
        if guards is not None and guards.clip > 0:
            pc_sub = _guards.apply_clip(snap0, pc_sub, factor)
        if accept is not None:
            # 0-weight x NaN = NaN: rejected rows must be zeroed out of
            # the average, not just down-weighted
            pc_sub = jax.tree.map(
                lambda p: jnp.where(
                    accept.reshape((-1,) + (1,) * (p.ndim - 1)) > 0,
                    p, jnp.zeros((), p.dtype)), pc_sub)
        cohort_avg = weighted_mean(pc_sub, jnp.take(r_hat, idx))
        mu = jnp.float32(mix_rate)
        global_c = jax.tree.map(lambda a: a[0], state.params["client"])
        new_global = jax.tree.map(
            lambda g, c: ((1.0 - mu) * g.astype(jnp.float32)
                          + mu * c.astype(jnp.float32)).astype(g.dtype),
            global_c, cohort_avg)

        # --- server half: in-scan updates (+ optional FedOpt on delta) ---
        new_ws = sub.params["server"]
        server_opt_state = afed.server_opt
        if server_optimizer is not None:
            ws_delta = jax.tree.map(
                lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                state.params["server"], new_ws)
            new_ws, server_opt_state = server_optimizer.update(
                ws_delta, server_opt_state, state.params["server"], server_lr)

        # --- cohort opt-state at the event boundary ---
        if delta:
            new_client = stack_client_params(new_global, 1)
            opt_c = jax.vmap(opt.init)(new_client)
        else:
            sub_opt_c = sub.opt_state["client"]
            if opt_state_policy == "reset":
                sub_opt_c = jax.vmap(opt.init)(sub.params["client"])
            elif opt_state_policy == "average":
                r_sub = jnp.take(r_hat, idx)

                def avg(a):
                    wb = r_sub.reshape((-1,) + (1,) * (a.ndim - 1))
                    m = (a.astype(jnp.float32) * wb).sum(axis=0) \
                        .astype(a.dtype)
                    return jnp.broadcast_to(m[None], a.shape)

                sub_opt_c = jax.tree.map(avg, sub_opt_c)
            if present is not None:
                # deadline-missed arrivals never delivered: keep their
                # pre-event moments
                sub_opt_c = jax.tree.map(
                    lambda o0, o1: jnp.where(
                        present.reshape((-1,) + (1,) * (o1.ndim - 1)) > 0,
                        o1, o0),
                    sub0.opt_state["client"], sub_opt_c)
            opt_c = engine.scatter_rows(state.opt_state["client"], sub_opt_c,
                                        idx)
            new_client = stack_client_params(new_global, K)

        # --- re-dispatch the cohort at the new version ---
        new_version = afed.server_version + 1
        k_delay, k_carry = jax.random.split(key_rest)
        new_delays = delays.sample(k_delay, (cohort,)).astype(jnp.float32)
        eff_delays = new_delays
        if stall_sub is not None:
            # stalled clients straggle for stall_factor x the sampled
            # delay; deadline/backoff later rescues the schedule
            eff_delays = jnp.where(stall_sub > 0,
                                   eff_delays * jnp.float32(
                                       faults.stall_factor),
                                   eff_delays)
        new_retries = None
        if present is not None:
            boff = jnp.power(jnp.float32(backoff),
                             retries_sub.astype(jnp.float32))
            eff_delays = jnp.where(present > 0, eff_delays,
                                   new_delays * boff)
            new_retries = jnp.where(present > 0, 0,
                                    retries_sub + 1).astype(jnp.int32)
        if delta:
            slot = new_version % ring_size
            snap = afed.client_params
            ring = jax.tree.map(
                lambda r, g: r.at[slot].set(g.astype(r.dtype)),
                afed.ring, new_global)
            ring_versions = afed.ring_versions.at[slot].set(new_version)
        else:
            rows = stack_client_params(new_global, cohort)
            if present is not None:
                # absent arrivals keep computing from their original
                # snapshot — only present ones restart from the new one
                rows = jax.tree.map(
                    lambda r, s0: jnp.where(
                        present.reshape((-1,) + (1,) * (r.ndim - 1)) > 0,
                        r.astype(s0.dtype), s0),
                    rows, snap0)
            snap = engine.scatter_rows(afed.client_params, rows, idx)
            ring, ring_versions = afed.ring, afed.ring_versions
        ver_sub = jnp.full((cohort,), new_version, jnp.int32)
        if present is not None:
            ver_sub = jnp.where(present > 0, ver_sub,
                                jnp.take(afed.version, idx)).astype(jnp.int32)
        retries_out = afed.retries
        if new_retries is not None:
            retries_out = afed.retries.at[idx].set(new_retries)
        new_afed = AsyncFedState(
            client_params=snap,
            version=afed.version.at[idx].set(ver_sub),
            server_version=new_version,
            finish_time=afed.finish_time.at[idx].set(t_event + eff_delays),
            now=t_event,
            key=k_carry,
            agg_state=agg_state,
            server_opt=server_opt_state,
            ring=ring,
            ring_versions=ring_versions,
            retries=retries_out,
            guard=new_guard_state)
        new_state = engine.TrainState(
            params={"client": new_client, "server": new_ws},
            opt_state={"client": opt_c, "server": sub.opt_state["server"]},
            step=sub.step)
        metrics = dict(metrics)
        if emit_client_metrics:
            metrics.update(
                arrival_mask=arrival_mask, staleness=staleness,
                staleness_mean=(staleness * arrival_mask).sum()
                / jnp.maximum(arrival_mask.sum(), 1.0))
        else:
            metrics.update(staleness_mean=jnp.take(staleness, idx).mean())
        metrics.update(t_event=t_event, server_version=new_version)
        if guards is not None:
            metrics.update(guard_accept=accept, guard_norm=g_norms,
                           guard_rejected=rejected)
        if present is not None:
            metrics.update(deadline_missed=jnp.float32(cohort)
                           - present.sum())
        if paged_opt:
            return new_state, new_afed, metrics, sub.opt_state["client"]
        return new_state, new_afed, metrics

    return async_fn


# ---------------------------------------------------------------------------
# the manual-SPMD ("lace_dp") event program
# ---------------------------------------------------------------------------


def _half_specs(tree, client_spec):
    """{'client','server'} pytree -> PartitionSpecs: client leaves on
    ``client_spec``, server leaves replicated."""
    from jax.sharding import PartitionSpec as P

    return {"client": jax.tree.map(lambda _: client_spec, tree["client"]),
            "server": jax.tree.map(lambda _: P(), tree["server"])}


def _make_async_runner_dp(model, scala, *, boundary, delays, cohort, opt,
                          sched,
                          ce_chunk, staleness_decay, mix_rate, agg,
                          server_optimizer, server_lr, opt_state_policy,
                          unroll, precision, delta, ring_size,
                          emit_client_metrics, arrival, mesh, batch_specs):
    """The whole async event inside one ``shard_map`` (backend lace_dp).

    See :func:`make_async_runner` — this builds the same
    ``async_fn(state, afed, round_batches, data_sizes=None)`` with the
    client axis sharded over the mesh's client axes and a *per-shard*
    cohort pop (each shard waits for ``cohort / n_shards`` of its local
    finishers — the balanced two-tier schedule).
    """
    from jax.sharding import PartitionSpec as P

    from repro.sharding.logical import round_specs

    if mesh is None or batch_specs is None:
        raise ValueError("backend 'lace_dp' needs mesh= and batch_specs=")
    axes = engine.mesh_axes(mesh)
    n_shards = engine.client_shard_count(mesh)
    if cohort % n_shards:
        raise ValueError(f"cohort {cohort} must divide over the {n_shards} "
                         "client shards (per-shard balanced pop)")
    if agg.shard_local is None:
        raise ValueError(
            f"aggregator {agg.name!r} is not shard-decomposable "
            "(Aggregator.shard_local is None); the lace_dp event needs "
            "fedavg / weighted / hierarchical")
    if agg.stateful:
        raise ValueError(f"aggregator {agg.name!r} is stateful; the lace_dp "
                         "async event supports stateless aggregators only")
    if opt_state_policy == "average":
        raise ValueError("opt_state_policy 'average' is not supported on "
                         "the lace_dp async event; use 'carry' or 'reset'")
    cohort_l = cohort // n_shards
    cspec = P(axes.client or None)
    rb_specs = round_specs(batch_specs)
    m_specs = {"loss_server": P(), "loss_client": P(), "aux": P(),
               "staleness_mean": P(), "t_event": P(), "server_version": P()}
    if emit_client_metrics:
        m_specs.update(arrival_mask=cspec, staleness=cspec)

    @trace.scoped("fed")
    def async_fn(state: engine.TrainState, afed: AsyncFedState,
                 round_batches, data_sizes=None):
        K = afed.version.shape[0]
        if K % n_shards:
            raise ValueError(f"{K} client slots must divide over the "
                             f"{n_shards} client shards")
        if delta and opt_state_policy == "carry" \
                and jax.tree.leaves(state.opt_state["client"]):
            raise ValueError(
                "snapshots='delta' cannot carry per-client optimizer "
                "moments; use a stateless optimizer or "
                "opt_state_policy='reset'")
        if jax.tree.leaves(round_batches)[0].shape[1] != K:
            raise ValueError("the lace_dp async event needs full (T, K, ...)"
                             " round_batches (sharded over the client axes)")
        if data_sizes is None:
            data_sizes = jnp.ones((K,), jnp.float32)

        pspec = P() if delta else cspec
        s_specs = engine.TrainState(
            params=_half_specs(state.params, pspec),
            opt_state=_half_specs(state.opt_state, pspec),
            step=P())
        a_specs = AsyncFedState(
            client_params=jax.tree.map(lambda _: cspec, afed.client_params),
            version=cspec, server_version=P(), finish_time=cspec, now=P(),
            key=P(),
            agg_state=jax.tree.map(lambda _: P(), afed.agg_state),
            server_opt=jax.tree.map(lambda _: P(), afed.server_opt),
            ring=jax.tree.map(lambda _: P(), afed.ring),
            ring_versions=P() if delta else (),
            retries=jax.tree.map(lambda _: cspec, afed.retries),
            guard=jax.tree.map(lambda _: P(), afed.guard))

        def body(st, af, rb, sizes_l):
            # --- per-shard pop of the local cohort (arrival= picks the
            # lexsort or the O(K_l)-work top-k; same schedule either way)
            idx, a_mask_l, t_l = arrival_cohort(af.finish_time, cohort_l,
                                                af.version, method=arrival)
            t_event = (jax.lax.pmax(t_l, axes.client) if axes.client
                       else t_l)
            stal_l = (af.server_version - af.version).astype(jnp.float32)

            # --- gather the local arrivals' snapshots ---
            if delta:
                snap_c, _ = ring_lookup(af.ring, jnp.take(af.version, idx),
                                        af.server_version, ring_size)
                sub = engine.TrainState(
                    params={"client": snap_c,
                            "server": st.params["server"]},
                    opt_state={"client": jax.vmap(opt.init)(snap_c),
                               "server": st.opt_state["server"]},
                    step=st.step)
            else:
                sub = engine._gather_clients(
                    engine.TrainState(
                        params={"client": af.client_params,
                                "server": st.params["server"]},
                        opt_state=st.opt_state, step=st.step), idx)
            sub_b = jax.tree.map(lambda a: jnp.take(a, idx, axis=1), rb)

            def step_body(s, b):
                grads, mets = engine.split_step_grads(
                    model, s.params, b, scala, backend="lace_dp",
                    boundary=boundary, ce_chunk=ce_chunk, axes=axes,
                    precision=precision)
                return engine._apply_updates(opt, s, grads,
                                             sched(s.step)), mets

            sub, ms = jax.lax.scan(step_body, sub, sub_b, unroll=unroll)
            metrics = dict(jax.tree.map(lambda a: a[-1], ms))

            # --- two-tier delayed aggregation: each shard (edge) folds
            # its cohort locally, the psum folds the edges ---
            w_base_l = agg.shard_local(a_mask_l, sizes_l, axes.client,
                                       n_shards)
            decay_l = jnp.power(jnp.float32(staleness_decay), stal_l)
            raw_l = w_base_l * decay_l * a_mask_l
            denom = raw_l.sum()
            if axes.client:
                denom = jax.lax.psum(denom, axes.client)
            r_l = raw_l / jnp.maximum(denom, 1e-8)
            part = weighted_mean(sub.params["client"], jnp.take(r_l, idx))
            cohort_avg = (jax.tree.map(
                lambda a: jax.lax.psum(a, axes.client), part)
                if axes.client else part)
            mu = jnp.float32(mix_rate)
            global_c = jax.tree.map(lambda a: a[0], st.params["client"])
            new_global = jax.tree.map(
                lambda g, c: ((1.0 - mu) * g.astype(jnp.float32)
                              + mu * c.astype(jnp.float32)).astype(g.dtype),
                global_c, cohort_avg)

            # --- server half (replicated; identical on every shard) ---
            new_ws = sub.params["server"]
            so_state = af.server_opt
            if server_optimizer is not None:
                ws_delta = jax.tree.map(
                    lambda a, b: (a.astype(jnp.float32)
                                  - b.astype(jnp.float32)),
                    st.params["server"], new_ws)
                new_ws, so_state = server_optimizer.update(
                    ws_delta, so_state, st.params["server"], server_lr)

            # --- opt state / re-dispatch (local slots) ---
            new_version = af.server_version + 1
            k_delay, k_carry = jax.random.split(af.key)
            shard_ix = jnp.int32(0)
            for a in axes.client:
                shard_ix = shard_ix * dict(mesh.shape)[a] \
                    + jax.lax.axis_index(a)
            new_delays = delays.sample(
                jax.random.fold_in(k_delay, shard_ix),
                (cohort_l,)).astype(jnp.float32)
            if delta:
                new_client = stack_client_params(new_global, 1)
                opt_c = jax.vmap(opt.init)(new_client)
                slot = new_version % ring_size
                snap = af.client_params
                ring = jax.tree.map(
                    lambda r, g: r.at[slot].set(g.astype(r.dtype)),
                    af.ring, new_global)
                ring_versions = af.ring_versions.at[slot].set(new_version)
            else:
                sub_opt_c = sub.opt_state["client"]
                if opt_state_policy == "reset":
                    sub_opt_c = jax.vmap(opt.init)(sub.params["client"])
                opt_c = engine.scatter_rows(st.opt_state["client"],
                                            sub_opt_c, idx)
                new_client = stack_client_params(new_global,
                                                 af.version.shape[0])
                snap = engine.scatter_rows(
                    af.client_params,
                    stack_client_params(new_global, cohort_l), idx)
                ring, ring_versions = af.ring, af.ring_versions
            new_af = AsyncFedState(
                client_params=snap,
                version=af.version.at[idx].set(new_version),
                server_version=new_version,
                finish_time=af.finish_time.at[idx].set(t_event + new_delays),
                now=t_event,
                key=k_carry,
                agg_state=af.agg_state,
                server_opt=so_state,
                ring=ring,
                ring_versions=ring_versions,
                retries=af.retries,
                guard=af.guard)
            new_st = engine.TrainState(
                params={"client": new_client, "server": new_ws},
                opt_state={"client": opt_c,
                           "server": sub.opt_state["server"]},
                step=sub.step)
            s_sum = (stal_l * a_mask_l).sum()
            s_cnt = a_mask_l.sum()
            if axes.client:
                s_sum = jax.lax.psum(s_sum, axes.client)
                s_cnt = jax.lax.psum(s_cnt, axes.client)
            if emit_client_metrics:
                metrics.update(arrival_mask=a_mask_l, staleness=stal_l)
            metrics.update(staleness_mean=s_sum / jnp.maximum(s_cnt, 1.0),
                           t_event=t_event, server_version=new_version)
            return new_st, new_af, metrics

        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(s_specs, a_specs, rb_specs, cspec),
            out_specs=(s_specs, a_specs, m_specs), check_vma=False)
        return fn(state, afed, round_batches, data_sizes)

    return async_fn
