"""Records the small profiler trace the trace-reduction tests read
(benchmarks/tests/data/tpu_small.xplane.pb). Run on the chip, by hand:

    python benchmarks/tests/record_fixture.py <out_dir>

Three runs of one small jitted program under two nested benchmark spans,
with a sleep between them so the idle gaps are known to be there.
"""
import glob
import os
import shutil
import sys
import time


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp

    def fixture_program(x):
        return jnp.sort(x @ x.T, axis=0)

    f = jax.jit(fixture_program)
    x = jnp.ones((1024, 1024), jnp.float32)
    f(x).block_until_ready()
    tmp = os.path.join(out_dir, "raw")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    t0 = time.perf_counter()
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.outer"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.sleep"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    window_s = time.perf_counter() - t0
    path = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    shutil.copy(path, os.path.join(out_dir, "tpu_small.xplane.pb"))
    shutil.rmtree(tmp)
    print("window_s", window_s, "bytes",
          os.path.getsize(os.path.join(out_dir, "tpu_small.xplane.pb")),
          jax.devices()[0].device_kind)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
