import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=512")

"""Compile one (arch, shape) pair and print the loop-aware per-op
collective ranking — the dry-run 'profiler' used by §Perf.

  PYTHONPATH=src python -m repro.launch.profile_collectives \
      --arch qwen1.5-0.5b --shape train_4k [--multi-pod] [--save /tmp/x.txt]
"""  # noqa: E402

import argparse  # noqa: E402

import jax  # noqa: E402

from repro.launch.dryrun import build_step  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.perf.roofline import collective_breakdown  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--top", type=int, default=15)
    ap.add_argument("--save", default="")
    ap.add_argument("--no-constrain", action="store_true")
    args = ap.parse_args()

    if args.no_constrain:
        from repro.sharding import logical
        logical.CONSTRAIN = False

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    step, fargs, in_sh, out_sh, meta, cfg = build_step(
        args.arch, args.shape, mesh)
    with jax.set_mesh(mesh):
        hlo = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh) \
            .lower(*fargs).compile().as_text()
    if args.save:
        with open(args.save, "w") as f:
            f.write(hlo)
    items, total = collective_breakdown(hlo, top=args.top)
    print(f"{args.arch} {args.shape} total={total:.3e} B/device "
          f"t_coll={total/50e9:.2f}s")
    for b, op, shape, mult, opn in items:
        print(f"{b:10.3e} ({100*b/total:4.1f}%) x{mult:<4} {op:18s} "
              f"{shape:44s} {opn}")


if __name__ == "__main__":
    main()
