"""The device's idle time in a trace, put down to the program's own spans.

    python3 chipbench/tools/idle_by_span.py chipbench/out/<cell>/trace [prefix]

``xplane.idle_by_host_activity``'s rule (each gap between device operations
goes to the shortest host event over its middle) applied to the events whose
name starts with ``prefix`` (``fedml.``: the spans the program opens on its hot
paths) and to no others, so that a gap reads ``fedml.serve.fetch`` and not the
runtime call inside it.  Prints seconds a span and the share of the idle time
that lies under none.  Reads a trace any ``--trace 1`` run left; anywhere."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def idle_by_span(trace, prefix: str = "fedml."):
    """``{span name: idle ns}`` over the first device of an ``xplane.Trace``,
    and the busy ns."""
    from chipbench.harness import xplane

    ops = xplane.first_device(trace)
    spans = [h for h in trace.host if h.name.startswith(prefix)]
    return xplane.idle_by_host_activity(ops, spans), xplane.busy_ns(ops)


def main(where: str, prefix: str = "fedml.") -> None:
    from chipbench.harness import xplane

    path = where if where.endswith(".pb") else xplane.newest_xplane(where)
    if path is None:
        raise SystemExit(f"no .xplane.pb under {where}")
    by, busy = idle_by_span(xplane.load(path), prefix)
    idle = sum(by.values())
    print(f"{path}\nbusy {busy / 1e9:.4f} s, idle {idle / 1e9:.4f} s "
          f"({100 * idle / max(busy + idle, 1):.1f}% of first to last "
          f"operation)")
    for name, ns in sorted(by.items(), key=lambda kv: -kv[1]):
        print(f"  {ns / 1e9:9.4f} s  {100 * ns / max(idle, 1):5.1f}%  {name}")


if __name__ == "__main__":
    main(*sys.argv[1:3])
