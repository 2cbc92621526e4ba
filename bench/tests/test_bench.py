"""CPU tests of the benchmark harness (``pytest bench/tests``).

They run the harness at reduced widths on the CPU, with the device check
stubbed where a test drives a whole run: the reference round against the
program, the faults that must turn ``correct`` false, the per-round work
check, the FLOP counts against hand counts, the peak table, the trace
reduction on a trace recorded on a TPU v5e, and the look-up of a cell's
files by name.
"""
from __future__ import annotations

import copy
import dataclasses
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness as H  # noqa: E402
from bench import peaks, span_reduce, trace_reduce  # noqa: E402
from bench import run as R  # noqa: E402
from bench.models import alexnet_split, transformer_lm  # noqa: E402

QWEN = "qwen1.5-0.5b.k4-masked"
ALEX = "alexnet-cifar.k100-sparse"
TRACE = ROOT / "bench" / "tests" / "data" / "tpu_v5e_trace.xplane.pb"
SPANS = ROOT / "bench" / "tests" / "data" / "tpu_v5e_spans.xplane.pb"
# program vs reference at float32 on the CPU: the same math in another
# order, so every gap is round-off (measured 1e-8 to 3e-5), the three
# rounds' losses and worst leaves too
CPU_GAP = 1e-4


def tiny_qwen() -> H.Cell:
    """The qwen cell at the program's reduced width (--reduced)."""
    c = H.load_cell(QWEN)
    c.config = copy.deepcopy(c.config)
    c.traffic = copy.deepcopy(c.traffic)
    c.config["spec"]["argv"] = c.config["spec"]["argv"] + ["--reduced"]
    sz = dict(num_layers=4, d_model=256, num_heads=4, num_kv_heads=2,
              head_dim=64, d_ff=512, vocab_size=512, split_layer=2)
    c.config["sizes"].update(sz)
    c.config["program_config"].update(sz, dtype="float32")
    argv = c.traffic["spec"]["argv"]
    argv[argv.index("--seq") + 1] = "16"
    c.traffic["expect"].update(rows_per_step=2 * 2 * 16, seq_len=16)
    return c


def tiny_alex() -> H.Cell:
    """The AlexNet cell (full width) with 10 clients of 100 samples."""
    c = H.load_cell(ALEX)
    c.config = copy.deepcopy(c.config)
    c.traffic = copy.deepcopy(c.traffic)
    f = c.traffic["spec"]["fields"]
    f["scala"].update(num_clients=10, participation=0.2, server_batch=32,
                      local_iters=2)
    f["fed"]["participation"] = "uniform:0.2"
    f["data"].update(n_train=1000, n_test=100)
    c.traffic["data"].update(clients=10, per_client=100)
    c.traffic["expect"].update(slots=10, participants=2, local_iters=2,
                               rows_per_step=32)
    return c


def cpu_facts(chips):
    return ({"platform": "cpu", "kind": "cpu", "count": 1},
            peaks.lookup("TPU v5 lite"))


def run_cell(cell, seed=2**31 + 11, seconds=1.0):
    args = R.parse(["--workload", cell.name, "--seed", str(seed),
                    "--seconds", str(seconds), "--trace", "0"])
    return R.run(args, cell=cell, facts=cpu_facts)


# ---------------------------------------------------------------------------
# the reference against the program, and the faults it must catch
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("make", [tiny_qwen, tiny_alex],
                         ids=["qwen", "alexnet"])
def test_reference_round_matches_program(make):
    cell = make()
    res = run_cell(cell)
    got = {k: v["value"] for k, v in res["compared"].items()}
    assert got.pop("work_differences") == 0
    assert set(got) == set(cell.limits)
    assert set(res["numbers"]) >= {"loss_gap", "change_gap", "change_diff",
                                   "change_diff_median"}
    assert max(res["numbers"].values()) < CPU_GAP, res["numbers"]
    assert res["correct"] is True
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in
                                   cell.metrics("end_to_end")}


def test_fused_rounds_per_call_are_checked_round_by_round():
    cell = tiny_qwen()
    cell.traffic["spec"]["argv"] += ["--rounds-per-call", "2"]
    res = run_cell(cell)
    assert res["compared"]["work_differences"]["value"] == 0
    assert max(res["numbers"].values()) < CPU_GAP, res["numbers"]
    assert res["correct"] is True and res["attempted"] % 2 == 0


def _state_unchanged(step):
    import jax
    import jax.numpy as jnp

    def broken(state, batches, sizes):
        _, metrics = step(jax.tree.map(jnp.copy, state), batches, sizes)
        return state, metrics

    return broken


def _half_batch(step):
    def broken(state, batches, sizes):
        w = batches["weights"]
        rows = w.shape[2]
        batches = dict(batches, weights=w.at[:, :, rows // 2:].set(0))
        return step(state, batches, sizes)

    return broken


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   H.stale_slots],
                         ids=["state_unchanged", "half_batch",
                              "stale_slots"])
@pytest.mark.parametrize("make", [tiny_qwen, tiny_alex],
                         ids=["qwen", "alexnet"])
def test_fault_in_timed_path_is_not_correct(make, fault, monkeypatch):
    from repro import api

    real_build = api.build

    def build(spec, **kw):
        program = real_build(spec, **kw)
        return dataclasses.replace(program, step=fault(program.step))

    monkeypatch.setattr(api, "build", build)
    cell = make()
    res = run_cell(cell)
    assert res["correct"] is False
    assert max(res["compared"][k]["value"] / lim
               for k, lim in cell.limits.items()) > 1.0


# ---------------------------------------------------------------------------
# a traced run: what it hands to the per-layer readers
# ---------------------------------------------------------------------------

OLD_METRICS = {"idle_share.train", "step_mfu.train",
               "device_ms_per_round.train"}
STAGE_METRICS = {f"{st}_ms_per_round.train" for st in (
    "client", "trunk", "boundary", "update", "fed", "unscoped")}
SPAN_METRICS = {"batches_idle_ms_per_round.train",
                "sync_idle_ms_per_round.train", "h2d_ms_per_round.train"}
HOST_METRICS = SPAN_METRICS | {"upload_mb_per_round.train"}


def run_traced(monkeypatch, rounds=2):
    """A ``--trace 1`` run of the tiny AlexNet cell whose traced window
    drives ``rounds`` real rounds and leaves the recorded TPU trace as
    its profile. Returns the result, the ``ctx`` the readers got, the
    bytes the window uploaded, and the cell."""
    seen = {}

    def traced_window(self, seconds, directory):
        tr = self.trainer
        seen["upload_bytes"] = tr.upload_bytes
        for _ in range(rounds):
            tr.step()
        seen["upload_bytes"] = tr.upload_bytes - seen["upload_bytes"]
        shutil.copy(SPANS, Path(directory) / SPANS.name)
        return rounds

    real_reader = H.metric_reader
    ctxs = []

    def metric_reader(name, root=ROOT):
        read = real_reader(name, root)
        return lambda ctx: ctxs.append(ctx) or read(ctx)

    monkeypatch.setattr(H.Job, "traced_window", traced_window)
    monkeypatch.setattr(H, "metric_reader", metric_reader)
    cell = tiny_alex()
    args = R.parse(["--workload", cell.name, "--seed", str(2**31 + 5),
                    "--seconds", "1", "--trace", "1"])
    res = R.run(args, cell=cell, facts=cpu_facts)
    return res, ctxs[0], seen, cell


def test_traced_run_hands_spans_counters_and_rounds_to_the_readers(
        monkeypatch):
    res, ctx, seen, cell = run_traced(monkeypatch)
    assert res["correct"] is True and res["attempted"] == 2
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(got) >= OLD_METRICS | STAGE_METRICS | HOST_METRICS
    s = span_reduce.summarize(str(SPANS))
    assert got["trunk_ms_per_round.train"] == 1000.0 * s.stage_s["trunk"] / 2
    assert got["h2d_ms_per_round.train"] == 1000.0 * s.h2d_s / 2
    assert got["upload_mb_per_round.train"] == (
        seen["upload_bytes"] / 1e6 / 2) > 0
    # every public int counter of the Trainer, as a change over the window
    assert ctx["counters"]["round"] == ctx["counters"]["gathered_rounds"] == 2
    assert ctx["counters"]["resident_bytes"] == 0     # the pool went up before
    assert [sorted(h) for h in ctx["round_metrics"]] == 2 * [
        sorted(ctx["round_metrics"][0])]
    assert "loss_server" in ctx["round_metrics"][0]
    assert ctx["cell"] is cell and ctx["rounds"] == 2


def _raises(path):
    raise RuntimeError("no framework_op_stats table")


def _no_scopes(path):
    return span_reduce.SpanSummary(
        unscoped_s=0.01, span_idle_s={"trainer.batches": 0.002,
                                      "trainer.sync": 0.0},
        h2d_s=0.001, h2d_events=4)


@pytest.mark.parametrize("summarize, missing", [
    (_raises, STAGE_METRICS | SPAN_METRICS), (_no_scopes, STAGE_METRICS)],
    ids=["reduction_fails", "no_scopes"])
def test_traced_run_leaves_out_what_it_could_not_read(monkeypatch, summarize,
                                                      missing):
    # a failed span reduction keeps the trace's own metrics; a cached
    # executable compiled without the scopes reads as not measured, not 0
    monkeypatch.setattr(span_reduce, "summarize", summarize)
    res, _, _, _ = run_traced(monkeypatch)
    got = set(res["metrics"])
    assert not got & missing
    assert got >= (OLD_METRICS | STAGE_METRICS | HOST_METRICS) - missing
    assert res["correct"] is True


def test_limits_are_set_for_every_cell():
    bm = H.load_json(ROOT / "BENCHMARK.json")
    w = {"client": {"a": np.ones(2)}, "server": {"b": np.ones(2)}}
    norms = {"['client']['a']": 1.0, "['server']['b']": 1.0}
    r = H.Readings([[1.0, 1.0]], norms, norms, w, w)
    names = set(H.compare(r, r))
    for w in bm["workloads"]:
        lim = H.load_cell(w["name"]).limits
        assert lim and set(lim) <= names
        assert all(0 < v < 1 for v in lim.values())


def test_gaps_scale_by_the_median_and_skip_leaves_that_do_not_move():
    ref = H.Readings([[2.0, 0.001], [2.0, 0.001]],
                     {"a": 1.0, "b": 1e-6, "c": 2.0},
                     {"a": 1.0, "b": 1e-6, "c": 2.0})
    prog = H.Readings([[2.0, 0.002], [2.2, 0.001]],
                      {"a": 1.1, "b": 5e-6, "c": 2.0},
                      {"a": 1.0, "b": 1.0, "c": 2.3})
    got = H.compare(prog, ref)
    # a near-zero eq. 15 loss is measured against the median loss (1.0005)
    assert got["loss_gap"] == (pytest.approx(0.1), "round 1 eq14")
    assert got["first_loss_gap"] == (pytest.approx(0.001 / 1.0005),
                                     "round 0 eq15")
    # leaf b moved under 1e-3 of the median leaf: not compared
    assert H.moving_leaves(ref) == ["a", "c"]
    assert got["first_update_gap"] == (pytest.approx(0.1 / 1.5), "a")
    assert got["first_update_median"][0] == pytest.approx(0.1 / 3)
    assert got["change_gap"] == (pytest.approx(0.15), "c")
    assert got["change_median"][0] == pytest.approx(0.075)
    assert "first_update_diff" not in got      # no weights in the readings


def test_diff_gaps_are_norms_of_the_weight_difference():
    w0 = {"layers": {"w": np.zeros((2, 3))}, "b": np.zeros(4)}
    w1 = {"layers": {"w": np.array([[3.0, 4, 0], [0, 0, 0]])},
          "b": np.full(4, 0.5)}
    d = H.diff_norms(w1, w0)
    assert d == {"['b']": 1.0, "['layers']['w'][0]": 5.0,
                 "['layers']['w'][1]": 0.0}
    norms = {k: 10.0 for k in d}
    ref = H.Readings([[1.0, 1.0]], norms, norms, w0, w0)
    prog = H.Readings([[1.0, 1.0]], norms, norms, w1, w1)
    got = H.compare(prog, ref)
    assert got["first_update_diff"] == (0.5, "['layers']['w'][0]")
    assert got["first_update_gap"][0] == 0.0


def test_nested_program_config_is_compared_field_by_field():
    cell = tiny_qwen()
    cell.config["spec"]["argv"] = ["--arch", "qwen3-moe-30b-a3b",
                                   "--precision", "f32", "--reduced"]
    cell.config["program_config"] = {
        "num_layers": 4, "moe": {"num_experts": 4, "top_k": 2,
                                 "router_aux_weight": 0.01}}
    assert H.make_spec(cell, 1).model_config().moe.top_k == 2
    cell.config["program_config"]["moe"]["top_k"] = 8
    with pytest.raises(ValueError, match="moe.top_k is 2, the configuration "
                       "file says 8"):
        H.make_spec(cell, 1)
    cell.config["program_config"]["moe"] = {"experts_held": 4}
    with pytest.raises(ValueError, match="moe has no field 'experts_held'"):
        H.make_spec(cell, 1)
    # a dense program has no moe block to compare
    qwen = H.load_cell(QWEN)
    assert H.make_spec(qwen, 1).model_config().moe is None
    qwen.config = copy.deepcopy(qwen.config)
    qwen.config["program_config"]["moe"] = {"top_k": 2}
    with pytest.raises(ValueError, match="moe is None"):
        H.make_spec(qwen, 1)


# ---------------------------------------------------------------------------
# the per-round work check
# ---------------------------------------------------------------------------


def _fed(cell, rounds=2, participants=None, rows=None):
    exp = cell.traffic["expect"]
    m = participants or exp["participants"]
    T, S = exp["local_iters"], exp["seq_len"]
    rng = np.random.default_rng(0)
    bk = exp["rows_per_step"] // (m * S)
    out = []
    for _ in range(rounds):
        w = np.ones((T, m, bk, S), np.float32)
        if rows is not None:
            w.reshape(-1)[rows:] = 0
        out.append({"tokens": rng.integers(0, 9, (T, m, bk, S)),
                    "labels": np.zeros((T, m, bk, S), np.int32),
                    "weights": w, "sizes": np.ones((m,), np.float32)})
    return H.Fed(out, exp["slots"])


def test_work_check_accepts_the_cells_work_and_names_differences():
    cell = H.load_cell(QWEN)
    assert H.work_check(cell, _fed(cell)) == []
    bad = H.work_check(cell, _fed(cell, participants=1))
    assert any("participants" in d for d in bad)
    bad = H.work_check(cell, _fed(cell, rows=100))
    assert any("tokens, expected 1024" in d for d in bad)
    fed = _fed(cell)
    fed.rounds[1]["tokens"] = fed.rounds[0]["tokens"]
    assert any("repeats" in d for d in H.work_check(cell, fed))


# ---------------------------------------------------------------------------
# FLOP counts, peaks
# ---------------------------------------------------------------------------


def test_transformer_flops_match_a_hand_count():
    config = {"sizes": dict(num_layers=3, d_model=4, num_heads=2,
                            num_kv_heads=1, head_dim=2, d_ff=6,
                            vocab_size=10, split_layer=1)}
    expect = {"rows_per_step": 5, "local_iters": 2, "seq_len": 3}
    # one layer's forward per token: q 2*4*4 + o 2*4*4 + k 2*4*2 + v
    # 2*4*2 = 96; SwiGLU 3 * 2*4*6 = 144; attention 2*2*2*(3+1) = 32
    proj, att = 96 + 144, 32
    client = 3 * (proj + att)                          # 1 client layer
    server = 2 * (4 * proj + 5 * att) - (32 + 16 + 16)  # q, k, v dgrad
    boundary = 4 * 2 * 4 * 10
    assert transformer_lm.round_flops(config, expect) == 10 * (
        client + server + boundary)


def test_alexnet_flops_match_a_hand_count():
    config = {"sizes": dict(conv_channels=[2, 3, 4, 5, 6], fc_widths=[7, 8],
                            vocab_size=10, image_hw=8, image_channels=1,
                            client_convs=2)}
    expect = {"rows_per_step": 3, "local_iters": 2}
    # 3x3 same convs at 8x8, 4x4 (after pool), 2x2, 2x2, 2x2; pool -> 1x1
    f = [2 * 64 * 9 * 1 * 2, 2 * 16 * 9 * 2 * 3, 2 * 4 * 9 * 3 * 4,
         2 * 4 * 9 * 4 * 5, 2 * 4 * 9 * 5 * 6, 2 * 6 * 7, 2 * 7 * 8,
         2 * 8 * 10]
    mult = [2, 3, 3, 4, 4, 4, 4, 4]
    assert alexnet_split.round_flops(config, expect) == 6 * sum(
        a * b for a, b in zip(f, mult))


def test_peak_lookup_refuses_unknown_devices():
    assert peaks.lookup("TPU v5 lite").flops_bf16 == 197e12
    with pytest.raises(ValueError):
        peaks.lookup("TPU v99")
    with pytest.raises(SystemExit):
        H.device_facts(1)           # the tests run on the CPU


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------


def test_interval_union_and_gaps():
    u = trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert u == [(0, 3), (5, 8)]
    assert trace_reduce.gaps(u, 0, 10) == [(3, 5), (8, 10)]
    assert trace_reduce.clip(u, 2, 6) == [(2, 3), (5, 6)]
    assert trace_reduce.short(
        "%fusion.3 = f32[8,4]{1,0:T(8,128)S(1)} fusion(f32[8]{0} %p), "
        "kind=kOutput") == "%fusion.3 fusion f32[8,4]"
    assert trace_reduce.short(
        "%copy-start = (f32[2]{0}, u32[]{:S(2)}) copy-start(f32[2]{0} "
        "%a)") == "%copy-start copy-start tuple"


def test_reduction_of_a_recorded_tpu_trace():
    s = trace_reduce.summarize(str(TRACE))
    assert TRACE.stat().st_size < 1 << 20
    assert s.devices == 1 and s.steps >= 1
    assert 0 < s.busy_s <= s.window_s
    idle = sum(v for _, v in s.idle_gaps)
    assert idle <= s.window_s - s.busy_s + 1e-9
    assert s.top_ops and all(v > 0 for _, v in s.top_ops)
    assert sum(v for _, v in s.top_ops) <= s.window_s + 1e-9


# ---------------------------------------------------------------------------
# files found by name, and what a bare checkout does
# ---------------------------------------------------------------------------


def test_cells_metrics_and_added_files_are_found_by_name(tmp_path):
    bm = H.load_json(ROOT / "BENCHMARK.json")
    for w in bm["workloads"]:
        assert H.load_cell(w["name"]).name == w["name"]
    for m in bm["per_layer"]:
        assert callable(H.metric_reader(m["name"]))
    # a later PR adds a configuration, a traffic mix, a cell and a metric
    # as new files plus entries, editing no file that is there
    root = tmp_path / "repo"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    b = root / "bench"
    shutil.copy(b / "configs" / "qwen1.5-0.5b.json",
                b / "configs" / "new-lm.json")
    shutil.copy(b / "traffic" / "k4-masked.json", b / "traffic" / "k2.json")
    (b / "metrics" / "rounds.train.py").write_text(
        "def read(ctx):\n    return float(ctx['rounds'])\n")
    bm["workloads"].append({"name": "new-lm.k2", "config": "new-lm",
                            "traffic": "k2", "chips": 1, "why": "test"})
    bm["per_layer"].append({"name": "rounds.train", "unit": "rounds",
                            "better": "higher", "source": "program_counter",
                            "layer": "x", "moves": "round_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    cell = H.load_cell("new-lm.k2", root=root)
    assert cell.config["name"] == "qwen1.5-0.5b" and cell.limits == {}
    assert "rounds.train" in [m["name"] for m in cell.metrics("per_layer")]
    assert H.metric_reader("rounds.train", root=root)({"rounds": 4}) == 4.0


def _run_cli(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", ALEX, "--seed", "1",
         "--seconds", "1", "--trace", "0", *extra], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_no_accelerator_exits_nonzero_without_a_result():
    p = _run_cli(ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_bare_checkout_exits_nonzero_without_a_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cli(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""
