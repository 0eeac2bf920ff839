"""Record the small TPU trace the tests reduce:

    python3 -m perfbench.tools.record_fixture --out chiprun_out/fixture

Five executions of one small jitted program (`fixture_step`: a matrix
product and a reduction) under `perfbench.step` spans, with a host sleep
under `perfbench.idle` between them so that the chip has idle gaps to
attribute. Needs a chip; the file it writes is kept in
`tests/perfbench/`.
"""
from __future__ import annotations

import argparse
import glob
import os
import shutil
import sys
import tempfile
import time

from perfbench import harness


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    harness.require_chips(len(jax.devices()))

    @jax.jit
    def fixture_step(x):
        return jnp.tanh(x @ x).sum(axis=0)

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    fixture_step(x).block_until_ready()
    tmp = tempfile.mkdtemp(prefix="perfbench_fixture_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with harness.span("trace_window"):
        for _ in range(5):
            with harness.span("step"):
                fixture_step(x).block_until_ready()
            with harness.span("idle"):
                time.sleep(0.002)
    jax.profiler.stop_trace()
    files = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    os.makedirs(args.out, exist_ok=True)
    dest = os.path.join(args.out, "small_tpu_trace.xplane.pb")
    shutil.copy(files[0], dest)
    shutil.rmtree(tmp, ignore_errors=True)
    print(dest, os.path.getsize(dest), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
