"""Look at a trace by hand before writing code against it:

    python3 -m perfbench.tools.trace_dump <file.xplane.pb> [events per line]

Prints every plane, its lines with their event counts, and the first
events of each line with their statistics.
"""
from __future__ import annotations

import sys


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    import jax
    show = int(argv[1]) if len(argv) > 1 else 5
    profile = jax.profiler.ProfileData.from_file(argv[0])
    for plane in profile.planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            for e in events[:show]:
                stats = {k: (str(v)[:80]) for k, v in e.stats}
                print(f"    {e.name[:100]!r} start {e.start_ns:.0f} dur "
                      f"{e.duration_ns:.0f} {stats}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
