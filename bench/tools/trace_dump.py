#!/usr/bin/env python3
"""Print the structure of a profiler trace: its planes and lines, the
operations that took most time on each device line, and the statistics
one event carries.  For reading one trace by hand before matching event
names in a metric's file.

    python3 bench/tools/trace_dump.py <trace dir>
"""

from __future__ import annotations

import glob
import sys
from pathlib import Path


def main() -> int:
    from jax.profiler import ProfileData

    (path,) = glob.glob(str(Path(sys.argv[1]) / "**" / "*.xplane.pb"),
                        recursive=True)
    print(f"trace {path} ({Path(path).stat().st_size} bytes)")
    prof = ProfileData.from_file(path)
    for plane in prof.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            print(f"  line {line.name!r}: {len(evs)} events")
            if not plane.name.startswith("/device"):
                names = sorted({str(e.name) for e in evs})
                print(f"    names: {names[:40]}")
                continue
            tot: dict = {}
            for e in evs:
                tot[e.name] = tot.get(e.name, 0) + e.duration_ns
            for name, ns in sorted(tot.items(), key=lambda kv: -kv[1])[:25]:
                print(f"    {ns / 1e6:12.3f} ms  {name}")
            for e in evs[:3]:
                print(f"    event {e.name!r} start={e.start_ns} "
                      f"dur={e.duration_ns} stats={dict(e.stats)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
