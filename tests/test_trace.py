"""The names the round carries into a profiler trace (``repro.perf.trace``):
the ``scala.*`` scopes in the compiled round, the Trainer's host spans
and its upload counters, and that none of them changes what is computed.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

import jax
import numpy as np
import pytest

from repro import api
from repro.configs import ScalaConfig
from repro.perf import trace


def _lm_spec(mode="masked", backend="lace", rpc=1):
    return api.ExperimentSpec(
        arch="qwen1.5-0.5b", reduced=True, rounds=4, seed=3,
        scala=ScalaConfig(num_clients=4, participation=0.5, local_iters=2,
                          server_batch=4),
        fed=api.FedSpec(participation="uniform:0.5"),
        execution=api.ExecutionSpec(mode=mode, backend=backend,
                                    rounds_per_call=rpc),
        data=api.DataSpec(kind="lm_synthetic", seq=16, docs_per_client=4))


def _image_spec(rpc=1):
    return api.ExperimentSpec(
        arch="alexnet-cifar", method="scala", rounds=4, seed=3,
        scala=ScalaConfig(num_clients=4, participation=0.5, local_iters=2,
                          server_batch=8, lr=0.05),
        fed=api.FedSpec(participation="uniform:0.5"),
        execution=api.ExecutionSpec(mode="sparse", rounds_per_call=rpc),
        data=api.DataSpec(kind="image_synthetic", n_train=200, alpha=2))


def _stage(op_name):
    """The stage an op is attributed to: the rightmost ``scala.<stage>``
    component of its ``op_name``."""
    found = re.findall(r"(?:^|[/(])scala\.(\w+)(?=$|[/)])", op_name)
    return found[-1] if found else None


def test_scope_names_a_known_stage():
    with pytest.raises(ValueError, match="unknown stage"):
        trace.scope("server")


@pytest.mark.parametrize("mode", ("masked", "sparse"))
@pytest.mark.parametrize("backend", ("logits", "lace"))
def test_round_hlo_carries_every_stage_scope(backend, mode):
    prog = api.build(_lm_spec(mode, backend))
    tr = api.Trainer(_lm_spec(mode, backend), program=prog)
    batches, sizes = tr._next_round_batches()
    hlo = prog.step.lower(tr.state, batches, sizes).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', hlo)
    stages = {_stage(n) for n in names}
    assert set(trace.STAGES) <= stages
    # backward ops keep the forward's scope, and the local steps' stages
    # are named after the round's scala.fed
    backward = {_stage(n) for n in names if "transpose(" in n}
    assert {"client", "trunk"} <= backward
    assert all(_stage(n) != "fed" for n in names
               if "scala.trunk" in n or "scala.update" in n)


class _Feed:
    """Wraps a program's step and sums the bytes of what it is fed, and
    of the int32 row ids (one per label) and sizes that select it."""

    def __init__(self, step):
        self.step, self.nbytes, self.row_bytes = step, 0, 0

    def __call__(self, state, batches, sizes):
        self.nbytes += sum(a.nbytes for a in jax.tree.leaves(
            (batches, sizes)))
        self.row_bytes += batches["labels"].size * 4 + sizes.nbytes
        return self.step(state, batches, sizes)


@pytest.mark.parametrize("make,rpc", [(_image_spec, 1), (_lm_spec, 1),
                                      (_lm_spec, 2)],
                         ids=["image", "lm", "lm-rounds_per_call-2"])
def test_upload_bytes_counts_the_fed_batches(make, rpc):
    spec = make(rpc=rpc)
    prog = api.build(spec)
    feed = _Feed(prog.step)
    tr = api.Trainer(spec, program=dataclasses.replace(prog, step=feed))
    assert tr.upload_bytes == 0
    tr.step()
    tr.step()
    assert tr.round == 2 * rpc
    if spec.data.kind == "image_synthetic":
        # image rounds are gathered on the device: the pool goes up once,
        # then each round's row ids and sizes
        assert tr.resident_bytes > feed.row_bytes > 0
        assert tr.upload_bytes == tr.resident_bytes + feed.row_bytes
    else:
        assert feed.nbytes > 0 and tr.upload_bytes == feed.nbytes


def _host_event_names(directory):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                        recursive=True)
    pd = ProfileData.from_file(path)
    return [ev.name for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


def test_history_is_bit_identical_under_the_profiler(tmp_path):
    spec = _image_spec()
    prog = api.build(spec)
    plain = api.Trainer(spec, program=prog)
    plain.run(3)
    traced = api.Trainer(spec, program=prog)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        traced.run(3)
    finally:
        jax.profiler.stop_trace()
    assert traced.history == plain.history
    for a, b in zip(jax.tree.leaves(traced.state), jax.tree.leaves(
            plain.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    names = _host_event_names(str(tmp_path))
    assert names.count("trainer.batches") == 3
    assert names.count("trainer.sync") == 3

