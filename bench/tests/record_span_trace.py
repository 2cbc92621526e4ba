"""Record the small TPU trace that ``test_span_reduce.py`` reads.

    python3 bench/tests/record_span_trace.py OUT.xplane.pb

Three ``round`` step annotations, as the harness's traced window makes
them, with the profiler's Python tracer off. In each: a
``trainer.batches`` span that builds a host array and puts it on the
device, then a jitted function with a ``lax.scan`` under
``scala.trunk``, a sort under ``scala.fed`` (no fusion hides it) and
unscoped reductions, then a ``trainer.sync`` span that pulls the
result to the host. Prints the reductions and the host events the
trace holds.
"""
from __future__ import annotations

import shutil
import sys
import tempfile
import time
from collections import Counter
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench import span_reduce, trace_reduce  # noqa: E402

N = 2048


@jax.jit
def f(x, w):
    with jax.named_scope("scala.trunk"):
        y, _ = jax.lax.scan(lambda c, _: (jnp.tanh(c @ w), None), x, None,
                            length=4)
    with jax.named_scope("scala.fed"):
        z = jnp.sort(y, axis=-1)
    return z[:, -1].sum() + x.mean()


def main(out: str) -> None:
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((N, N), np.float32) / N)
    float(f(jnp.zeros((N, N), jnp.float32), w))          # compile
    tmp = tempfile.mkdtemp(prefix="span_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    for i in range(3):
        with jax.profiler.StepTraceAnnotation("round", step_num=i):
            with jax.profiler.TraceAnnotation("trainer.batches"):
                xh = rng.standard_normal((N, N), np.float32)
                time.sleep(0.002)
                x = jax.device_put(xh)
            y = f(x, w)
            with jax.profiler.TraceAnnotation("trainer.sync"):
                float(y)
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(tmp)
    shutil.copyfile(path, out)
    shutil.rmtree(tmp, ignore_errors=True)
    print(trace_reduce.summarize(out))
    print(span_reduce.summarize(out))
    pd = trace_reduce._load(out)
    for plane in pd.planes:
        names = Counter(ev.name for line in plane.lines
                        for ev in line.events)
        print(plane.name, dict(names))


if __name__ == "__main__":
    main(sys.argv[1])
