#!/usr/bin/env python3
"""Print what a profiler trace holds, to look at one by hand before the
reduction in ``xplane.py`` is trusted: planes, lines, event counts, the
names that took most time on each line, and one event's stats.

    python3 chipbench/tools/dump_trace.py <file.xplane.pb | trace dir> [top]
"""

import collections
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from chipbench import xplane  # noqa: E402


def main(path, top=15):
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = xplane.newest(path)
    print(path, os.path.getsize(path), "bytes")
    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            if not events:
                continue
            t = collections.Counter()
            for e in events:
                t[e.name] += e.duration_ns
            span = (min(e.start_ns for e in events),
                    max(e.start_ns + e.duration_ns for e in events))
            print(f"  LINE {line.name!r}: {len(events)} events, "
                  f"{(span[1] - span[0]) / 1e6:.3f} ms from {span[0]}")
            for name, ns in t.most_common(top):
                print(f"    {ns / 1e6:10.3f} ms  {name[:150]}")
            e = max(events, key=lambda e: e.duration_ns)
            print("    stats of the longest:",
                  {k: str(v)[:200] for k, v in e.stats})


if __name__ == "__main__":
    main(sys.argv[1], *(int(a) for a in sys.argv[2:3]))
