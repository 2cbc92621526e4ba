"""Readings that set a cell's limits, on the chip, in one process.

    python3 bench/control.py --workload <name> --seeds 1 2 3 ... \
        [--controls 3] [--out FILE]

For every seed: the program's first rounds (the same set-up path as a
benchmark run, one built program shared by all seeds) against the plain
reference, i.e. the readings of sound runs. For the first ``--controls``
seeds also: the control (the reference with its matmul operands rounded
to the configuration's ``control`` dtype, put in the program's place),
the planted half-batch fault (the reference with half of each client's
rows left out) and the planted stale-slots fault (the program with the
aggregate kept from every client slot but the first). A state left
unchanged reads about 1 by construction and is not run.

With ``--witness`` the program runs in float32 throughout (the
configuration's activation dtype replaced, every matmul at precision
``highest``), so that its gaps to the reference are those of float32
round-off: a gap that stays is a difference of the computation, not of
its precision.

One JSON line per seed on standard output, every number of
``harness.compare`` with where it was worst; the benchmark's own runs
never run this.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def float32_program(cell):
    """Run the program in float32 throughout (the ``--witness`` run)."""
    import jax
    from repro.api import specs

    jax.config.update("jax_default_matmul_precision", "highest")
    model_config = specs.ExperimentSpec.model_config
    specs.ExperimentSpec.model_config = lambda self: dataclasses.replace(
        model_config(self), dtype="float32")
    cell.config["program_config"]["dtype"] = "float32"


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--out", default=None)
    ap.add_argument("--witness", action="store_true")
    args = ap.parse_args(argv)

    from bench import harness as H
    from bench import scala_ref
    from repro.launch.compile_cache import use_compile_cache

    cell = H.load_cell(args.workload)
    H.device_facts(cell.workload["chips"])
    if args.witness:
        float32_program(cell)
    use_compile_cache()
    q = scala_ref.quantizer(cell.config["control"]["dtype"])
    program = None
    lines = []
    for i, seed in enumerate(args.seeds):
        t = time.perf_counter()
        job = H.Job(cell, seed, program=program)
        program = job.program
        prog, fed = job.check_rounds()
        job.close()
        del job
        gc.collect()
        ref = H.reference_readings(cell, seed, fed)

        def compare(readings):
            return H.compare(readings, ref, fed.per_call)

        row = {"seed": seed, "work": H.work_check(cell, fed),
               "program": compare(prog), "losses": prog.losses,
               "ref_losses": ref.losses}
        if i < args.controls:
            row["control"] = compare(
                H.reference_readings(cell, seed, fed, q=q))
            row["half_batch"] = compare(
                H.reference_readings(cell, seed, fed, transform=H.half_batch))
            job = H.Job(cell, seed, program=dataclasses.replace(
                program, step=H.stale_slots(program.step)))
            stale, _ = job.check_rounds()
            job.close()
            del job
            gc.collect()
            row["stale_slots"] = compare(stale)
        H.log(f"seed {seed}: {time.perf_counter() - t:.3f} s")
        print(json.dumps(row), flush=True)
        lines.append(row)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with open(args.out, "w") as f:
            for row in lines:
                f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
