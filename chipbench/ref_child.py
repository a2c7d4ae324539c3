"""The process that runs the serving reference (that of the configuration's
family) once the replica has exited and given the chip back: reads a sample
of served requests, writes every gap.  Platform as the run's (the chip, or
the CPU in a rehearsal)."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def main(argv=None) -> None:
    p = argparse.ArgumentParser(prog="chipbench-ref-child")
    p.add_argument("--config", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--sample", required=True)
    p.add_argument("--out", required=True)
    p.add_argument("--platform", required=True)
    p.add_argument("--cache-dir", required=True)
    p.add_argument("--control", type=int, default=0)
    args = p.parse_args(argv)
    t0 = time.monotonic()
    with open(args.config) as f:
        conf = json.load(f)
    with open(args.sample) as f:
        sample = json.load(f)

    import jax

    from . import families

    if jax.devices()[0].platform != args.platform:
        raise SystemExit(f"reference: platform {jax.devices()[0].platform}, asked {args.platform}")
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", args.cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    rows = families.of(conf).served_gaps(
        conf, args.seed, sample["cases"], sample["pad_to"], bool(args.control)
    )
    with open(args.out, "w") as f:
        json.dump({"rows": rows, "seconds": time.monotonic() - t0}, f)
    print(f"reference: {len(rows)} cases in {time.monotonic() - t0:.1f} s", file=sys.stderr)


if __name__ == "__main__":
    main()
