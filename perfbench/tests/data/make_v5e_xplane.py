"""Records `v5e.xplane.pb`: a real profiler trace from a TPU v5e, for the
test of the scope and host-span reduction (`perfbench/xscopes.py`).

    python3 perfbench/tests/data/make_v5e_xplane.py

Run on the chip: it exits 1 on any other backend. A jitted step sorts a
matrix by columns inside one `jax.named_scope` and then by rows outside
it; the compiler adds copies and iotas that carry no `op_name` at all.
Three calls of it run under one `perfbench.window` annotation, with the
Python tracer off as the benchmark's own profile has it. The window opens
and closes with a pause of `PAUSE_S`: in a trace this short the v5e's ops
sit about a millisecond before the host calls that launch them.
"""
import pathlib
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

SCOPE = "fixture.scope"
OUT = pathlib.Path(__file__).with_name("v5e.xplane.pb")
PAUSE_S = 0.02


@jax.jit
def step(x):
    with jax.named_scope(SCOPE):
        y = jnp.sort(x, axis=0)
    return jnp.sort(y, axis=1)


def main() -> int:
    if jax.default_backend() != "tpu":
        print(f"needs a TPU, the backend is {jax.default_backend()}",
              file=sys.stderr)
        return 1
    x = jax.random.uniform(jax.random.key(0), (512, 512), jnp.float32)
    step(x).block_until_ready()
    d = tempfile.mkdtemp(prefix="v5e-fixture-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation("perfbench.window"):
        time.sleep(PAUSE_S)
        for _ in range(3):
            x = step(x)
        x.block_until_ready()
        time.sleep(PAUSE_S)
    jax.profiler.stop_trace()
    found = sorted(pathlib.Path(d).rglob("*.xplane.pb"))
    shutil.copyfile(found[-1], OUT)
    shutil.rmtree(d, ignore_errors=True)
    print(f"wrote {OUT} ({OUT.stat().st_size} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
