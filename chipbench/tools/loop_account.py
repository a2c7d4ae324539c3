"""Builder's tool: where the owner loop's time went over one window of a
cell, from the phase counters on ``/metrics`` (models/engine_profiler.py),
and whether they account for the owner thread's time.  Launches as a run
does; prints a table on stderr, no line.

    python3 -m chipbench.tools.loop_account --workload mistral7b-d16.batch --seed 7 --seconds 40

Between the window's first and last sample of ``/metrics`` (the poller's,
every 0.5 s, each stamped on the harness's clock): the growth of the seven
step phases' counters plus idle over the time between the two stamps (at
each stamp at most one phase is open, and a phase reaches its counter when
it closes), each phase's share, each sub-phase beside its parent, and the
counts taken at the same places.
"""

from __future__ import annotations

import argparse
import signal
import sys
import time

from .. import run
from ..readers._loop import STEP_PHASES

SUB = {"start_prefill": "schedule", "prefill_chunk": "prefill", "graft": "prefill", "frontier": "dispatch", "clear_slot": None}
COUNTS = ("tpu_engine_steps_total", "tpu_engine_requests_total", "tpu_engine_decode_dispatches_block_total",
          "tpu_engine_decode_dispatches_step_total", "tpu_engine_prefill_chunks_total", "tpu_engine_cleared_slots_total")


def account(samples: list[tuple[float, dict[str, float]]]) -> dict:
    """The accounting between the first and the last sample."""
    (t0, a), (t1, b) = samples[0], samples[-1]
    grew = lambda name: b.get(name, 0.0) - a.get(name, 0.0)  # noqa: E731
    seconds = lambda phase: grew(f"tpu_engine_loop_{phase}_seconds_total")  # noqa: E731
    phases = {p: seconds(p) for p in STEP_PHASES + ("idle",)}
    return {
        "elapsed_s": t1 - t0, "accounted_s": sum(phases.values()), "phases": phases,
        "sub": {s: (seconds(s), parent, phases.get(parent)) for s, parent in SUB.items()},
        "counts": {c: grew(c) for c in COUNTS},
    }


def main() -> int:
    p = argparse.ArgumentParser(prog="chipbench.tools.loop_account")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rehearse", choices=["cpu"], default=None)
    args = p.parse_args()
    args.trace, args.keep, args.fault, args.control = 0, 0, "", 0
    ctx: dict = {"children": []}
    try:
        cell, env, run_dir, platform, _ = run.launch(args, ctx)
        server, port = run.start_replica(cell, args, env, run_dir, platform, ctx)
        t0 = time.monotonic()
        requests = run.make_requests(cell.traffic, args.seed, args.seconds, cell.config["model"]["vocab_size"])
        results, _, scraped = run.serve_window(cell, args, port, t0, requests)
        run.say_tails(results)
        got = account(scraped["samples"])
        run.say(f"loop account, {cell.name}: {got['accounted_s']:.3f} s in the seven step phases and idle of "
                f"{got['elapsed_s']:.3f} s between the scrapes = {100 * got['accounted_s'] / got['elapsed_s']:.2f} %")
        stepped = got["accounted_s"] - got["phases"]["idle"]
        for name, s in got["phases"].items():
            run.say(f"  {name:12s} {s:9.3f} s  {100 * s / got['accounted_s']:6.2f} % of the loop"
                    + (f"  {100 * s / stepped:6.2f} % of the steps" if name != "idle" and stepped > 0 else ""))
        for name, (s, parent, parent_s) in got["sub"].items():
            inside = f"{parent} {parent_s:.3f} s: {'inside' if s <= parent_s else 'OVER'}" if parent else "whichever phase ended the request"
            run.say(f"  {name:14s} {s:9.3f} s  in {inside}")
        run.say("  " + " ".join(f"{k.removeprefix('tpu_engine_').removesuffix('_total')}={v:.0f}" for k, v in got["counts"].items()))
        server.stop(signal.SIGTERM, grace=60)
    finally:
        run.teardown(ctx, keep=False)
    return 0


if __name__ == "__main__":
    sys.exit(main())
