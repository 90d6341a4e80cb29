#!/usr/bin/env python3
"""Record the small trace that ``selfcheck.py`` reduces (run once, on the
chip, by hand; the result is committed as ``benchmark/data/selfcheck.xplane.pb``).

Eight executions of one named program with a host sleep between them,
each inside a ``bench:`` annotation, so the reduction has device work,
idle gaps and host spans of known structure to find.

    python3 benchmark/tools/record_selfcheck_trace.py <out-dir>
"""

import glob
import os
import shutil
import sys
import tempfile
import time


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp

    @jax.jit
    def selfcheck_matmul(a, b):
        return jnp.tanh(a @ b).sum()

    a = jnp.ones((2048, 2048), jnp.bfloat16)
    b = jnp.ones((2048, 2048), jnp.bfloat16)
    selfcheck_matmul(a, b).block_until_ready()
    tmp = tempfile.mkdtemp(prefix="selfcheck_trace_")
    jax.profiler.start_trace(tmp)
    t0 = time.time()
    with jax.profiler.TraceAnnotation("bench:window"):
        for i in range(8):
            with jax.profiler.TraceAnnotation("bench:step"):
                selfcheck_matmul(a, b).block_until_ready()
            with jax.profiler.TraceAnnotation("bench:sleep"):
                time.sleep(0.02)
    window_s = time.time() - t0
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    os.makedirs(out_dir, exist_ok=True)
    dst = os.path.join(out_dir, "selfcheck.xplane.pb")
    shutil.copy(found[0], dst)
    shutil.rmtree(tmp, ignore_errors=True)
    print("wrote", dst, os.path.getsize(dst), "bytes; window_s", window_s,
          "device", jax.devices()[0].device_kind)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
