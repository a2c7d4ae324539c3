"""Record the small trace the tests keep (tests/chipbench/data/): a jitted
scan and a second, overlapping-in-lines program on every local device,
captured for longer than the work lasts, with the host's Python tracer off
so that the file stays small.

    python3 -m chipbench.tools.record_trace <out.xplane.pb>
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time


def main() -> None:
    out = sys.argv[1]
    import jax
    import jax.numpy as jnp

    @jax.jit
    def block(x):
        def body(c, _):
            return jnp.tanh(c @ c), None
        return jax.lax.scan(body, x, None, length=4)[0]

    @jax.jit
    def step(x):
        return jnp.tanh(x @ x)

    xs = [jax.device_put(jnp.eye(256, dtype=jnp.bfloat16), d) for d in jax.local_devices()]
    for x in xs:
        block(x).block_until_ready()
        step(x).block_until_ready()
    tdir = tempfile.mkdtemp(prefix="chipbench-record-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tdir, profiler_options=opts)
    time.sleep(0.05)
    for _ in range(3):
        outs = [block(x) for x in xs] + [step(x) for x in xs]
        jax.block_until_ready(outs)
        time.sleep(0.02)
    time.sleep(0.1)  # the capture outlasts the work
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tdir, "plugins", "profile", "*", "*.xplane.pb"))[0]
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    shutil.copy(path, out)
    shutil.rmtree(tdir, ignore_errors=True)
    print(f"{out}: {os.path.getsize(out)} bytes, {len(xs)} device(s)")


if __name__ == "__main__":
    main()
