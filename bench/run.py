"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload NAME --seed N --seconds S --trace 0|1

From the root of a checkout. Set-up builds the cell's program through
the repo's entry points (``api.build`` on the spec, ``api.Trainer``),
loads the benchmark's weights made from the seed, and drives the first
rounds through ``Trainer.step`` (that compiles or loads every program
the window uses, and records what the correctness check compares).
The window then runs whole rounds for ``--seconds``. After it, with the
program's state freed, the plain reference replays the first rounds
and the numbers compared are printed beside their limits.

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` runs
a short window (at most ``TRACE_SECONDS``) under the profiler and
prints the per-layer metrics. Each reader (``bench/metrics/<name>.py``,
``read(ctx)``) gets in ``ctx``: ``trace`` (``trace_reduce``'s busy time
and top ops), ``spans`` (``span_reduce``'s stage times, span idle and
transfers, or None where they could not be read), ``counters`` (the
change over the window of every public ``int`` attribute of the
``Trainer``), ``round_metrics`` (the window's per-round scalars),
``rounds``, ``peak``, ``flops_per_round`` and the ``cell``. The
last line of standard output is the result; everything else goes to
standard error. With no accelerator, or fewer chips than the cell
asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def read_spans(path: str):
    """``span_reduce``'s summary of the trace, or None (logged) where it
    cannot be read: the metrics that read the trace alone still print."""
    import traceback

    from bench import span_reduce
    from bench.harness import log

    t = time.perf_counter()
    try:
        spans = span_reduce.summarize(path)
    except Exception:       # noqa: BLE001 -- any failure leaves spans out
        log("span reduction failed; the metrics that read it are left "
            f"out:\n{traceback.format_exc()}")
        return None
    log(f"span reduction {time.perf_counter() - t:.3f} s: stages "
        f"{sorted(spans.stage_s)}, spans {sorted(spans.span_idle_s)}, "
        f"{spans.h2d_events} host-to-device transfers")
    return spans


def run(args, cell=None, facts=None) -> dict:
    """One run. ``cell`` and ``facts`` (the device check) default to the
    workload's files and the accelerator; tests hand in their own."""
    from bench import harness as H
    from bench import trace_reduce
    from repro.launch.compile_cache import use_compile_cache

    cell = cell or H.load_cell(args.workload)
    device, peak = (facts or H.device_facts)(cell.workload["chips"])
    use_compile_cache()
    H.log(f"{cell.name}: seed {args.seed}, {args.seconds} s, trace "
          f"{args.trace}, on {device}")

    job = H.Job(cell, args.seed)
    prog, fed = job.check_rounds()
    bad = H.work_check(cell, fed)
    setup_s = time.perf_counter() - T_START
    H.log(f"set-up {setup_s:.3f} s (build, init, weights, "
          f"{H.CHECK_ROUNDS} check rounds)")

    result = {"attempted": 0, "failed": 0}
    if args.trace:
        before, seen = H.counters(job.trainer), len(job.trainer.history)
        tmp = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            rounds = job.traced_window(min(args.seconds, H.TRACE_SECONDS),
                                       tmp)
            path = trace_reduce.find_xplane(tmp)
            summary = trace_reduce.summarize(path)
            spans = read_spans(path)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        after = H.counters(job.trainer)
        ctx = {"trace": summary, "rounds": rounds, "peak": peak,
               "flops_per_round": cell.family.round_flops(
                   cell.config, cell.traffic["expect"]),
               "spans": spans,
               "counters": {k: v - before[k] for k, v in after.items()
                            if k in before},
               "round_metrics": job.trainer.history[seen:],
               "cell": cell}
        metrics = {}
        for m in cell.metrics("per_layer"):
            v = H.metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["attempted"] = rounds
        device = dict(device, busy_s=summary.busy_s,
                      window_s=summary.window_s)
        result["breakdown"] = {"device_ops": summary.top_ops,
                               "idle_gaps": summary.idle_gaps}
        H.log(f"traced {rounds} rounds: window {summary.window_s:.6f} s, "
              f"busy {summary.busy_s:.6f} s, counters {ctx['counters']}")
    else:
        times, window_s, failed, wlog = job.window(args.seconds)
        result["attempted"], result["failed"] = len(times), failed
        values = {"round_s": window_s / len(times),
                  "round_p90_s": H.p90(times) if len(times) >= 2
                  else times[0], "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.metrics("end_to_end")}
        H.log(f"window {window_s:.6f} s, {len(times)} rounds, median "
              f"{statistics.median(times):.6f} s; slowest (round, s): "
              f"{H.slowest(times)}; in the window: {wlog}")

    device["memory_peak_bytes"] = H.memory_peak_bytes()
    job.close()
    del job
    gc.collect()

    t = time.perf_counter()
    with H.WindowLog() as rlog:
        ref = H.reference_readings(cell, args.seed, fed)
    t_cmp = time.perf_counter()
    numbers = H.compare(prog, ref, fed.per_call)
    H.log(f"reference {t_cmp - t:.3f} s ({rlog}), comparison "
          f"{time.perf_counter() - t_cmp:.3f} s; leaves not compared "
          f"(reference change below {H.NOUGHT} of the median leaf's): "
          f"{sorted(set(ref.first) - set(H.moving_leaves(ref)))}")
    for k, (v, at) in numbers.items():
        if k not in cell.limits:
            H.log(f"not compared {k}: {v!r}, worst at {at}")
    compared = {k: {"value": numbers[k][0], "limit": lim}
                for k, lim in cell.limits.items()}
    correct = (bool(compared) and not bad and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in compared.values()))
    compared["work_differences"] = {"value": len(bad), "limit": 0}
    for d in bad:
        H.log(f"work: {d}")
    result.update(correct=correct, metrics=metrics, device=device)
    result["compared"] = compared
    # every number read, compared or not (not printed: the log has them)
    result["numbers"] = {k: v for k, (v, _) in numbers.items()}
    for k, c in compared.items():
        at = f", worst at {numbers[k][1]}" if k in numbers else ""
        H.log(f"compared {k}: {c['value']!r} (limit {c['limit']!r}){at}")
    return result


def main(argv=None) -> int:
    args = parse(argv)
    result = run(args)
    order = ["correct", "attempted", "failed", "metrics", "device",
             "breakdown", "compared"]
    print(json.dumps({k: result[k] for k in order if k in result}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
