"""Look at a trace by hand: planes, lines, how many events, the first
few names. `python benchmark/tools/trace_look.py FILE.xplane.pb [N]`."""

import sys

from jax.profiler import ProfileData


def main(path: str, n: int = 8) -> None:
    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            for e in events[:n]:
                print(f"      {e.name[:90]!r} start_ns={e.start_ns:.0f} "
                      f"dur_ns={e.duration_ns:.0f}")


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 8)
