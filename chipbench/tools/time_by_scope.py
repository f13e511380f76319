"""A program's device time in a trace by the program's own scopes, and what
the largest kinds of operation hold.

    python3 chipbench/tools/time_by_scope.py chipbench/out/<cell>/trace [module] [steps]

``module`` is a pattern over the names on the ``XLA Modules`` line
(``jit_sft_epoch``, the default; ``jit_decode_multi_k8``); ``steps`` what one
execution is divided by (the cell's ``steps_per_call``; 1 left out).  First
the table: ms an execution (a step) by scope and direction, from
``harness/scopes.time_by_scope`` over every execution the trace holds.  Then
the twenty kinds of operation (``xplane.kind_of``) with most self time, each
with the scope its time went to and what it **holds**: the instructions XLA
fused into it (or a loop runs), counted by their own scopes.  Reads a trace
any ``--trace 1`` run left; anywhere."""

import collections
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def tables(trace, progs, module: str):
    """(``{(scope, direction): ns}``, ``{kind: [ns, events, {(scope,
    direction) of the event: ns}, holds]}``, executions) over the program's
    executions in the trace; None where `scopes.scoped_events` is."""
    from chipbench.harness import scopes, xplane

    found = scopes.scoped_events(trace, progs, module)
    if found is None:
        return None
    by, kinds = collections.Counter(), {}
    for ev, own, info in found:
        key = scopes.key_of(info)
        by[key] += own
        row = kinds.setdefault(xplane.kind_of(ev), [
            0.0, 0, collections.Counter(), collections.Counter()])
        row[0] += own
        row[1] += 1
        row[2][key] += own
        if info is not None:
            row[3].update(info.holds)
    return by, kinds, len(scopes.executions(trace, module))


def _held(holds, n: int = 6) -> str:
    total = sum(holds.values())
    return ", ".join(f"{scope} {d} {100 * c / total:.0f}%"
                     for (scope, d), c in holds.most_common(n)) or "itself"


def main(where: str, module: str = "jit_sft_epoch", steps: str = "1") -> None:
    from chipbench.harness import scopes, xplane

    path = where if where.endswith(".pb") else xplane.newest_xplane(where)
    if path is None:
        raise SystemExit(f"no .xplane.pb under {where}")
    got = tables(xplane.load(path), scopes.programs(path), module)
    if got is None:
        raise SystemExit(1)
    by, kinds, runs = got
    per = max(runs, 1) * int(steps) * 1e6
    total = sum(by.values())
    print(f"{path}\n{module}: {runs} executions, {total / per:.3f} ms of self "
          f"time an execution" + (f" over {steps} steps" if steps != "1"
                                  else ""))
    names = sorted({s for s, _ in by}, key=lambda s: -sum(
        ns for (t, _), ns in by.items() if t == s))
    print(f"  {'scope':28s} {'all':>9s} {'fwd':>9s} {'bwd':>9s} {'remat':>9s}"
          f" {'%':>6s}")
    for s in names:
        row = [by.get((s, d), 0.0) for d in ("fwd", "bwd", "remat", "")]
        print(f"  {s:28s} {sum(row) / per:9.3f} " + " ".join(
            f"{v / per:9.3f}" for v in row[:3])
            + f" {100 * sum(row) / max(total, 1):6.2f}")
    print("the kinds of operation with most self time, and what they hold:")
    for kind, (ns, n, where_to, holds) in sorted(
            kinds.items(), key=lambda kv: -kv[1][0])[:20]:
        to = ", ".join(f"{s} {d}".strip() for (s, d), _ in
                       where_to.most_common(3))
        print(f"  {ns / per:9.3f} ms x{n // max(runs, 1):<5d} {kind}\n"
              f"{'':24s}time to {to}; holds {_held(holds)}")


if __name__ == "__main__":
    main(*sys.argv[1:4])
