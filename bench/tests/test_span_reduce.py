"""CPU tests of ``bench/span_reduce.py`` (``pytest bench/tests``).

They read ``data/tpu_v5e_spans.xplane.pb``, recorded on a TPU v5e by
``record_span_trace.py``: three ``round`` annotations, each a
``trainer.batches`` span that uploads a 2048 x 2048 float32 array, a
jitted function with a scan under ``scala.trunk``, a sort under
``scala.fed`` and unscoped reductions, and a ``trainer.sync`` span. And
they check that the older recorded trace still reduces as it did, and
that the per-layer readers of the stages, spans and counters give the
reductions per round, or nothing where the trace has no such names.
"""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness as H  # noqa: E402
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


# ---------------------------------------------------------------------------
# the per-layer readers of the stage times, spans and counters
# ---------------------------------------------------------------------------

STAGES = ("client", "trunk", "boundary", "update", "fed")
SPAN_READERS = [f"{st}_ms_per_round.train" for st in STAGES] + [
    "unscoped_ms_per_round.train", "batches_idle_ms_per_round.train",
    "sync_idle_ms_per_round.train", "h2d_ms_per_round.train"]
READERS = SPAN_READERS + ["upload_mb_per_round.train"]


def read_all(ctx):
    return {n: H.metric_reader(n)(ctx) for n in READERS}


def test_readers_are_the_reductions_per_round(reduced):
    s, _ = reduced
    ctx = {"spans": s, "rounds": 3, "counters": {"upload_bytes": 3 * UPLOAD}}
    k = 1000.0 / 3
    want = {f"{st}_ms_per_round.train": s.stage_s.get(st, 0.0) * k
            for st in STAGES}
    want.update({
        "unscoped_ms_per_round.train": s.unscoped_s * k,
        "batches_idle_ms_per_round.train":
            s.span_idle_s["trainer.batches"] * k,
        "sync_idle_ms_per_round.train": s.span_idle_s["trainer.sync"] * k,
        "h2d_ms_per_round.train": s.h2d_s * k,
        "upload_mb_per_round.train": UPLOAD / 1e6})
    got = read_all(ctx)
    assert got == pytest.approx(want, rel=1e-12)
    # the recorded program has no client, boundary or update ops
    assert [got[f"{st}_ms_per_round.train"] for st in
            ("client", "boundary", "update")] == [0.0, 0.0, 0.0]
    assert all(got[n] > 0 for n in ("trunk_ms_per_round.train",
                                    "fed_ms_per_round.train"))


def test_readers_say_not_measured_where_the_names_are_missing():
    # the older trace: no scopes, no spans, no transfers; no counter
    old = {"spans": span_reduce.summarize(str(OLD)), "rounds": 3,
           "counters": {}}
    assert read_all(old) == dict.fromkeys(READERS)
    # the span reduction failed
    gone = {"spans": None, "rounds": 3, "counters": {"upload_bytes": 30}}
    assert read_all(gone) == dict(dict.fromkeys(SPAN_READERS),
                                  **{"upload_mb_per_round.train": 1e-5})


def test_readers_are_in_the_benchmark_for_every_training_cell():
    bm = H.load_json(ROOT / "BENCHMARK.json")
    entries = {m["name"]: m for m in bm["per_layer"]}
    for n in READERS:
        m = entries[n]
        assert (m["moves"], m["better"], "workloads" in m) == (
            "round_s", "lower", False)
        assert m["unit"] == ("MB" if n.startswith("upload") else "ms")
