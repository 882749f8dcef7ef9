"""Look at one trace by hand before writing code against it.

    python3 -m perfbench.tools.trace_dump <file.xplane.pb> [top]

Prints every plane and line with its event count, the first events of each
line with their stats, and the device operations and programs that took
most self time."""
from __future__ import annotations

import sys

from .. import trace


def main(path: str, top: int = 25) -> None:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print(f"  LINE {line.name!r}: {len(evs)} events")
            for e in evs[:3]:
                stats = [(k, str(v)[:80]) for k, v in list(e.stats)[:8]]
                print(f"    {e.name[:100]!r} start={e.start_ns} "
                      f"dur={e.duration_ns} {stats}")
    tr = trace.Trace(path)
    print("window", tr.window(), "busy", tr.busy_seconds())
    for kind in ("ops", "modules"):
        rows = sorted(tr.op_seconds(kind).items(), key=lambda kv: -kv[1])
        print(f"TOP {kind} by self seconds")
        for name, t in rows[:top]:
            print(f"  {t:10.6f}  {name[:120]}")
    print("idle gaps by span:", tr.idle_gaps())
    names = sorted({n for _, _, n in tr.spans})
    print("harness spans:", names)


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 25)
