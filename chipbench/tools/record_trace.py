"""Record the small chip trace that the trace reduction is tested on.

    python3 chipbench/tools/record_trace.py <out.xplane.pb>

On one TPU chip: two jitted programs, each called three times inside its
own benchmark span, with a 10 ms host sleep between them, all inside a
``chipbench.window`` span that opens and closes with a 20 ms sleep
(the chip's clock in the trace runs about a millisecond off the
host's, and the margin keeps every op inside the window).  The
committed ``chipbench/testdata/tiny.xplane.pb`` was written this way.
"""
import glob
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def main(out: str) -> None:
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_trace: needs a TPU")
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    g = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones((1024, 1024))
    f(x).block_until_ready()
    g(x).block_until_ready()
    d = tempfile.mkdtemp(prefix="chipbench-record-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation("chipbench.window"):
        with jax.profiler.TraceAnnotation("chipbench.sleep"):
            time.sleep(0.02)
        for _ in range(3):
            with jax.profiler.TraceAnnotation("chipbench.f"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("chipbench.sleep"):
                time.sleep(0.01)
            with jax.profiler.TraceAnnotation("chipbench.g"):
                g(x).block_until_ready()
        with jax.profiler.TraceAnnotation("chipbench.sleep"):
            time.sleep(0.02)
    jax.profiler.stop_trace()
    (path,) = glob.glob(d + "/**/*.xplane.pb", recursive=True)
    shutil.copy(path, out)
    shutil.rmtree(d)


if __name__ == "__main__":
    main(sys.argv[1])
