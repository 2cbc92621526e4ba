"""Names the SCALA round carries into a profiler trace.

Device scopes. Each stage of the compiled round is traced under
``jax.named_scope("scala.<stage>")``, which only sets the ``op_name``
metadata of the HLO ops it emits; the computation is unchanged. Ops of
a backward pass keep the forward's scope inside their
``transpose(jvp(...))`` component, and a stage nested in another (the
local steps inside the round's ``scala.fed``) names the inner one last,
so an op belongs to the rightmost ``scala.*`` component of its name.

Host spans. :func:`span` is a ``jax.profiler.TraceAnnotation``: it
lands on the profiler's own clock beside the device planes, and costs
one native check when no profiler session is active.
"""
from __future__ import annotations

import functools

import jax

PREFIX = "scala."

#: the stages of a round, as ``scala.<stage>`` scopes:
#: client - the client halves' forward (stage 2) and their pullback;
#: trunk - the server trunk's forward and its two pullbacks;
#: boundary - label priors (stage 1) and the eq. 14/15 losses and
#: their gradients at the split;
#: update - the optimizer step of each local iteration (stage 5);
#: fed - the round around the local steps: participation and fault
#: masks, slot gather and scatter, guards, aggregation
STAGES = ("client", "trunk", "boundary", "update", "fed")


def scope(stage: str):
    """The ``jax.named_scope`` of one of :data:`STAGES`."""
    if stage not in STAGES:
        raise ValueError(f"unknown stage {stage!r}; expected {STAGES}")
    return jax.named_scope(PREFIX + stage)


def scoped(stage: str):
    """Decorator: trace the function under :func:`scope` ``(stage)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with scope(stage):
                return fn(*args, **kwargs)
        return inner
    return wrap


def span(name: str) -> jax.profiler.TraceAnnotation:
    """A host span named ``name`` in the profiler's trace."""
    return jax.profiler.TraceAnnotation(name)
