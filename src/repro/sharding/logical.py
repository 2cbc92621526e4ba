"""Logical-axis -> mesh-axis resolution with divisibility fallback.

Every param/activation leaf carries a tuple of logical axis names (see
``axes()`` functions in the model library). This module maps them onto
the physical mesh: each logical name has an ordered candidate list of
mesh-axis groups; the first group whose axes (a) exist in the mesh,
(b) are not already used by an earlier dim of the same leaf and
(c) divide the dim size, wins. Otherwise the dim is replicated.

This gives, on the production (data, model) mesh:
  client/batch -> data (client-parallelism), vocab/heads/ffn/experts ->
  model (tensor/expert parallelism), embed -> data (FSDP for the
  server-side halves that must fit — Jamba 398B), with automatic
  replication fallback for the small-head archs (whisper 6H, xlstm 4H).
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

Axes = Tuple[str, ...]

# ordered candidates: each entry is a tuple of mesh axes used together
RULES: Dict[str, Tuple[Tuple[str, ...], ...]] = {
    "client": (("pod", "data"), ("data",)),
    "batch": (("pod", "data"), ("data",)),
    "cache_batch": (("pod", "data"), ("data",)),
    "cache_seq": (("data",), ("model",)),
    "seq": (),
    "vocab": (("model",),),
    "embed": (("data",),),
    "embed_alt": (),
    "heads": (("model",),),
    "kv_heads": (("model",),),
    "head_dim": (),
    "head_dim_alt": (),
    "ffn": (("model",),),
    "expert_ffn": (("model",), ("data",)),
    "experts": (("model",),),
    "experts_router": (("model",),),
    "inner": (("model",),),
    "inner_alt": (),
    "state": (),
    "conv_k": (),
    "lowrank": (),
    "gates": (),
    "gate_kind": (),
    "layers": (),
    "position": (),
    "frontend": (),
    "prefix": (),
    "per_client_batch": (),
}


_DP_OVERRIDES = {
    # pure data/client parallelism: weights replicated, batch over all axes
    "client": (("pod", "data"), ("data",)),
    "batch": (("pod", "data", "model"), ("data", "model"), ("pod", "data"),
              ("data",)),
    "cache_batch": (("pod", "data", "model"), ("data", "model"),
                    ("pod", "data"), ("data",)),
    "per_client_batch": (("model",),),
    "cache_seq": (),
    "vocab": (), "embed": (), "heads": (), "kv_heads": (), "ffn": (),
    "expert_ffn": (), "experts": (), "experts_router": (), "inner": (),
}

RULES_DP: Dict[str, Tuple[Tuple[str, ...], ...]] = {**RULES, **_DP_OVERRIDES}

# ZeRO-3/FSDP profile: no tensor parallelism at all — batch over every
# mesh axis (same as "dp"), weights *sharded* over every axis on their
# embed dim and all-gathered at use (mid/large archs whose weights do
# not fit replicated). The layer scan slices one layer's shard per trip,
# so the gather is per-layer, classic FSDP.
_FSDP_OVERRIDES = {
    **_DP_OVERRIDES,
    "embed": (("pod", "data", "model"), ("data", "model"), ("data",)),
    "expert_ffn": (("pod", "data", "model"), ("data", "model"), ("data",)),
}

RULES_FSDP: Dict[str, Tuple[Tuple[str, ...], ...]] = {**RULES,
                                                      **_FSDP_OVERRIDES}


def rules_for(profile: str) -> Dict[str, Tuple[Tuple[str, ...], ...]]:
    return {"dp": RULES_DP, "fsdp": RULES_FSDP}.get(profile, RULES)


def is_axes(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(s, str) for s in x)


def spec_for(axes: Axes, shape: Sequence[int], mesh: Mesh,
             rules: Optional[Dict] = None) -> PartitionSpec:
    rules = RULES if rules is None else rules
    sizes = dict(mesh.shape)
    used = set()
    entries = []
    assert len(axes) == len(shape), (axes, tuple(shape))
    for name, dim in zip(axes, shape):
        choice = None
        for group in rules.get(name, ()):
            if not all(a in sizes for a in group):
                continue
            if any(a in used for a in group):
                continue
            total = int(np.prod([sizes[a] for a in group]))
            if dim % total != 0:
                continue
            choice = group
            break
        if choice is None:
            entries.append(None)
        else:
            used.update(choice)
            entries.append(choice if len(choice) > 1 else choice[0])
    while entries and entries[-1] is None:
        entries.pop()
    return PartitionSpec(*entries)


def tree_specs(axes_tree, shape_tree, mesh: Mesh, rules=None):
    """Map (axes pytree, ShapeDtypeStruct pytree) -> PartitionSpec pytree."""
    flat_axes = jax.tree.leaves(axes_tree, is_leaf=is_axes)
    flat_shapes, treedef = jax.tree.flatten(shape_tree)
    assert len(flat_axes) == len(flat_shapes), (
        f"axes/shape tree mismatch: {len(flat_axes)} vs {len(flat_shapes)}")
    specs = [spec_for(a, s.shape, mesh, rules)
             for a, s in zip(flat_axes, flat_shapes)]
    return jax.tree.unflatten(treedef, specs)


def round_specs(batch_specs):
    """Per-step batch PartitionSpecs -> per-round (T-stacked) specs.

    Round-granular programs (``core.engine.make_round_runner``,
    ``fed.runtime`` async events) consume batches with a leading local-
    iteration axis T prepended to every per-step leaf; T is a time axis
    and never sharded, so each spec simply gains a leading ``None``.
    """
    return jax.tree.map(
        lambda s: PartitionSpec(None, *s), batch_specs,
        is_leaf=lambda s: isinstance(s, PartitionSpec))


def tree_shardings(axes_tree, shape_tree, mesh: Mesh, rules=None):
    specs = tree_specs(axes_tree, shape_tree, mesh, rules)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda s: isinstance(s, PartitionSpec))


def replicated(mesh: Mesh):
    return NamedSharding(mesh, PartitionSpec())


def client_scalar_spec(mesh: Mesh, n: int) -> PartitionSpec:
    """PartitionSpec for a (K,) per-client *schedule scalar* — the async
    runtime's ``version`` / ``finish_time`` tags and sampled delays
    (``fed.runtime.init_async_state(mesh=...)``). Resolves the "client"
    logical axis against this mesh with the standard divisibility
    fallback: replicated when K does not divide the client shard count.
    """
    return spec_for(("client",), (n,), mesh)


# ---------------------------------------------------------------------------
# in-graph activation constraints (§Perf iteration 1)
# ---------------------------------------------------------------------------

# Toggle for A/B measurement of the sharding-constraint optimization
# (dryrun --no-constrain reproduces the propagation-only baseline).
CONSTRAIN = True


def ambient_mesh():
    """The abstract mesh made ambient by ``jax.set_mesh`` (or a
    ``jax.shard_map`` body), or ``None`` when there is none."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def in_shard_map() -> bool:
    """True while tracing a ``jax.shard_map`` body: the ambient mesh then
    has Manual axes, data is already device-local, and sharding
    constraints / ambient-mesh collectives must be skipped."""
    mesh = ambient_mesh()
    return mesh is not None and bool(mesh.manual_axes)


def place(tree, shardings):
    """Lay ``tree`` out as ``shardings`` (a matching tree of
    ``NamedSharding``) inside jit: a sharding constraint on ``Auto``
    axes, ``jax.sharding.reshard`` on a mesh whose axes are all
    ``Explicit``, where a constraint would only assert."""
    def one(x, s):
        if s.mesh.are_all_axes_explicit:
            return jax.sharding.reshard(x, s)
        return jax.lax.with_sharding_constraint(x, s)

    return jax.tree.map(one, tree, shardings)


def constrain(x, *axes):
    """``with_sharding_constraint`` against the *ambient* abstract mesh.

    ``axes`` is one entry per dim: None, a mesh-axis name, or a tuple of
    names. Axes missing from the current mesh are dropped; a dim whose
    size does not divide the requested axes is left unconstrained. Under
    no mesh (CPU unit tests, host training) this is a no-op, so model
    code can call it unconditionally. It also holds under a mesh whose
    axes are all ``Explicit`` (the ``jax.make_mesh`` default): see
    :func:`place`.

    XLA's sharding propagation over the SCALA step has a failure mode
    where the server-trunk batch dim de-shards (involuntary full
    rematerialization -> every device computes the full concatenated
    batch). Pinning the residual stream's batch dim to ("pod","data")
    removes the redundant compute and collectives.
    """
    if not CONSTRAIN:
        return x
    mesh = ambient_mesh()
    if mesh is None:
        return x
    manual = set(mesh.manual_axes)  # inside shard_map: already local
    sizes = {k: v for k, v in dict(mesh.shape).items() if k not in manual}
    spec = []
    for a, dim in zip(axes, x.shape):
        if a is None:
            spec.append(None)
            continue
        names = a if isinstance(a, tuple) else (a,)
        names = tuple(n for n in names if n in sizes)
        total = int(np.prod([sizes[n] for n in names])) if names else 1
        if not names or dim % total != 0:
            spec.append(None)
        else:
            spec.append(names if len(names) > 1 else names[0])
    if all(s is None for s in spec):
        return x
    return place(x, NamedSharding(mesh, PartitionSpec(*spec)))
