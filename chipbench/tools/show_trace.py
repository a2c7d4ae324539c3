"""Print what a trace holds, to be read by hand before code is written
against it: planes, lines, event counts, the first names.

    python3 -m chipbench.tools.show_trace <file.xplane.pb | trace dir>
"""

from __future__ import annotations

import json
import os
import sys

from .. import trace


def main() -> None:
    path = sys.argv[1]
    if os.path.isdir(path):
        found = []
        for root, _, files in os.walk(path):
            found += [os.path.join(root, f) for f in files if f.endswith(".xplane.pb")]
        path = max(found, key=os.path.getmtime)
    print(path, os.path.getsize(path), "bytes")
    profile = trace.load(path)
    for plane in profile.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            timed = [e for e in events if e.duration_ns > 0]
            span = (min(e.start_ns for e in events), max(e.start_ns + e.duration_ns for e in events)) if events else None
            print(f"  LINE {line.name!r}: {len(events)} events, {len(timed)} with a duration, span {span}")
            for e in timed[:4]:
                print(f"      {e.name[:90]!r} start {e.start_ns} dur {e.duration_ns}")
    reduced = trace.reduce(profile)
    if reduced:
        reduced.pop("op_seconds")
        reduced["program_events"] = reduced["program_events"][:12]
        print(json.dumps(reduced, indent=1))


if __name__ == "__main__":
    main()
