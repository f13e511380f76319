"""Print what a trace holds: planes, lines, the commonest event names with
their statistics.  For looking at one trace by hand before writing a reader
against it."""

import collections
import sys


def main(path: str, top: int = 25) -> None:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            names = collections.Counter()
            dur = collections.Counter()
            for ev in events:
                names[ev.name] += 1
                dur[ev.name] += ev.duration_ns
            print(f"  LINE {line.name!r}: {len(events)} events")
            for name, d in dur.most_common(top):
                print(f"      {d / 1e6:10.3f} ms  x{names[name]:<6} {name[:110]}")
            for ev in events[:2]:
                print("      e.g.", ev.name[:80], dict(ev.stats))


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 25)
