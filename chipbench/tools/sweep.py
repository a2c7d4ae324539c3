"""Builder's tool: one window per load level on ONE warmed replica, to find
the highest rate (open loop) or client count (closed loop) that a cell's
replica sustains.  Launches as a run does (the daemon, ``Allocate``, the
child under the returned variables); prints a table on stderr, no line.

    python3 -m chipbench.tools.sweep --workload mistral7b-d16.chat --seed 111 --seconds 30 --levels 1,1.25,1.5
"""

from __future__ import annotations

import argparse
import signal
import sys
import time

from .. import run
from ..cells import Cell, load_reader


def main() -> int:
    p = argparse.ArgumentParser(prog="chipbench.tools.sweep")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--levels", required=True, help="rates (open loop) or client counts (closed), e.g. 1,1.25,1.5")
    p.add_argument("--rehearse", choices=["cpu"], default=None)
    args = p.parse_args()
    args.trace, args.keep, args.fault, args.control = 0, 0, "", 0
    ctx: dict = {"children": []}
    try:
        cell, env, run_dir, platform, _ = run.launch(args, ctx)
        server, port = run.start_replica(cell, args, env, run_dir, platform, ctx)
        vocab = cell.config["model"]["vocab_size"]
        key = "rate_rps" if cell.traffic["loop"] == "open" else "clients"
        for i, level in enumerate(args.levels.split(",")):
            spec = dict(cell.traffic, **{key: float(level) if key == "rate_rps" else int(level)})
            probe = Cell(**{**cell.__dict__, "traffic": spec})
            t0 = time.monotonic()
            results, _, scraped = run.serve_window(
                probe, args, port, t0, run.make_requests(spec, args.seed + i, args.seconds, vocab))
            run.say_tails(results)
            view = {"results": results, "window": (t0, args.seconds), "scraped": scraped,
                    "slots": cell.config["engine"]["slots"]}
            row = {name: load_reader(name)(view) for name in
                   ("ttft_p90_pre_capture_ms", "itl_p95_ms", "out_tokens_per_s", "queue_wait_mean_ms", "slot_occupancy")}
            late = sum(1 for r in results if r.token_times and r.token_times[-1] > t0 + args.seconds)
            run.say(f"sweep {key}={level}: sent {len(results)} failed {sum(1 for r in results if not r.done)} "
                    f"unfinished_at_close {late} " + " ".join(f"{k}={v:.1f}" for k, v in row.items() if v is not None))
        server.stop(signal.SIGTERM, grace=60)
    finally:
        run.teardown(ctx, keep=False)
    return 0


if __name__ == "__main__":
    sys.exit(main())
