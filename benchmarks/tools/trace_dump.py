"""Print the planes and lines of a profiler trace: the first thing to look
at when the reducer (harness/trace.py) meets a new runtime or device.

    python3 benchmarks/tools/trace_dump.py <dir-or-file.xplane.pb> [n]
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv) -> int:
    from jax.profiler import ProfileData

    from benchmarks.harness import trace as T

    path = argv[1] if argv[1].endswith(".pb") else T.find_xplane(argv[1])
    n = int(argv[2]) if len(argv) > 2 else 5
    print(path, os.path.getsize(path), "bytes")
    for plane in ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            ev = list(line.events)
            print(f"  LINE {line.name!r}: {len(ev)} events")
            for e in ev[:n]:
                print(f"     {e.name[:70]!r} start={e.start_ns:.0f} "
                      f"dur={e.duration_ns:.0f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
