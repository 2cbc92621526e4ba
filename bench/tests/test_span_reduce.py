"""CPU tests of ``bench/span_reduce.py`` (``pytest bench/tests``).

They read ``data/tpu_v5e_spans.xplane.pb``, recorded on a TPU v5e by
``record_span_trace.py``: three ``round`` annotations, each a
``trainer.batches`` span that uploads a 2048 x 2048 float32 array, a
jitted function with a scan under ``scala.trunk``, a sort under
``scala.fed`` and unscoped reductions, and a ``trainer.sync`` span. And
they check that the older recorded trace still reduces as it did.
"""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import span_reduce, trace_reduce  # noqa: E402

DATA = ROOT / "bench" / "tests" / "data"
SPANS = DATA / "tpu_v5e_spans.xplane.pb"
OLD = DATA / "tpu_v5e_trace.xplane.pb"
UPLOAD = 2048 * 2048 * 4


@pytest.fixture(scope="module")
def reduced():
    before = sorted(p.name for p in DATA.iterdir())
    s = span_reduce.summarize(str(SPANS))
    # xprof's cache goes beside a copy, not into the test data
    assert sorted(p.name for p in DATA.iterdir()) == before
    return s, trace_reduce.summarize(str(SPANS))


def test_stage_is_the_rightmost_scala_component():
    names = ["jit(step)/scala.fed/while/body/scala.trunk/transpose(jvp())/"
             "dot_general", "jit(f)/scala.fed/sort", "jit(f)/reduce_sum",
             "jit(step)/scala.fed/closed_call/vmap(transpose(jvp("
             "scala.client)))/mul", "src/repro/core/scala.py:12"]
    assert [span_reduce.stage_of(n) for n in names] == [
        "trunk", "fed", None, "client", None]


def test_overlap_of_interval_lists():
    assert span_reduce.overlap([(0, 4), (6, 9)], [(3, 7), (8, 20)]) == 3
    assert span_reduce.overlap([(0, 1)], [(1, 2)]) == 0
    assert span_reduce.overlap([], [(0, 5)]) == 0


def test_stage_self_times_and_remainder_make_the_busy_time(reduced):
    s, t = reduced
    assert set(s.stage_s) == {"trunk", "fed"}
    assert all(v > 0 for v in s.stage_s.values()) and s.unscoped_s > 0
    total = sum(s.stage_s.values()) + s.unscoped_s
    assert abs(total - t.busy_s) <= 0.01 * t.busy_s
    # the scan's four 2048^3 matmuls are the trunk's
    assert any("dot_general" in op for op, _ in s.stage_ops["trunk"])
    assert any("sort" in op for op, _ in s.stage_ops["fed"])


def test_idle_inside_each_span_is_attributed_to_it(reduced):
    s, t = reduced
    assert set(s.span_idle_s) == {"trainer.batches", "trainer.sync"}
    idle = t.window_s - t.busy_s
    assert sum(s.span_idle_s.values()) <= idle + 1e-9
    # the device waits while the host builds and uploads the batch
    assert s.span_idle_s["trainer.batches"] >= 0.8 * idle
    assert 0 < s.span_idle_s["trainer.sync"] < s.span_idle_s[
        "trainer.batches"]


def test_host_to_device_transfers_are_found(reduced):
    s, t = reduced
    assert s.h2d_events == 3 and s.h2d_bytes == 3 * UPLOAD
    assert 0 < s.h2d_s < t.window_s


def test_older_recorded_trace_reduces_as_before():
    t = trace_reduce.summarize(str(OLD))
    assert (t.window_s, t.busy_s, t.steps, t.devices) == (
        0.029499068000000003, 0.000362324, 3, 1)
    assert t.top_ops[:2] == [
        ("%fusion fusion f32[2048,2048]", 0.000182864),
        ("%convolution_tanh_fusion fusion bf16[2048,2048]", 0.000179426)]
    assert t.idle_gaps == [("np.asarray(jax.Array)", 0.019492702),
                           ("round", 0.009644042)]
    s = span_reduce.summarize(str(OLD))
    assert s.stage_s == {} and s.span_idle_s == {} and s.h2d_events == 0
    assert s.unscoped_s > 0
