"""Print what a ``.xplane.pb`` holds: planes, lines, the first events of each
line with their stats. Look at one trace by hand before trusting
``harness/trace.py`` on a new runtime.

    python3 benchmark/tools/trace_dump.py <trace dir or file> [events per line]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.harness import trace  # noqa: E402


def main(argv):
    from jax.profiler import ProfileData

    path = argv[0]
    if os.path.isdir(path):
        path = trace.find_xplane(path)
    per_line = int(argv[1]) if len(argv) > 1 else 5
    data = ProfileData.from_file(path)
    print(f"{path}: {os.path.getsize(path)} bytes")
    for plane in data.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            print(f"  line {line.name!r}: {len(events)} events")
            for ev in events[:per_line]:
                stats = {k: (v if len(str(v)) < 80 else str(v)[:77] + "...")
                         for k, v in ev.stats}
                print(f"    {ev.name[:100]!r} start {ev.start_ns} "
                      f"dur {ev.duration_ns} stats {stats}")


if __name__ == "__main__":
    main(sys.argv[1:])
