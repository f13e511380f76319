"""Device milliseconds a token step of the full ``decode_multi`` dispatch
spends on the weights' half: the scopes ``fedml.attn.qkv``,
``fedml.attn.out``, ``fedml.mlp``, ``fedml.head`` and ``fedml.norm`` inside
the executions of the variant ``decode_device_ms.variants_ns`` puts first,
over those executions and the ``k`` of the program's name
(``jit_decode_multi_k<k>``).  Read from the trace's own HLO
(``chipbench/harness/scopes.py``); nothing on a program that names no
scope."""

import re

from chipbench.harness import scopes, xplane
from chipbench.metrics.decode_device_ms import PROGRAM, variants_ns

DENSE = ("fedml.attn.qkv", "fedml.attn.out", "fedml.mlp", "fedml.head",
         "fedml.norm")


def full_dispatch_by_scope(run):
    """(`scopes.time_by_scope` of the full dispatch's program, its
    executions in the trace, its ``k``), once a run; None where the variants
    cannot be told apart or the program names no scope."""
    if "_scopes_decode" not in run.__dict__:
        run._scopes_decode = None
        found = variants_ns(run)
        by_name = {}
        if found:
            for m in xplane.matching(
                    xplane.first_device_modules(run.trace), PROGRAM):
                by_name.setdefault(m.name, []).append(m.dur)
        name = next((n for n, durs in by_name.items() if durs == found[0]),
                    None)
        k = re.search(r"_k(\d+)", name or "")
        by = k and scopes.time_by_scope(run.trace, scopes.of_run(run),
                                        "^" + re.escape(name) + "$")
        if by:
            run._scopes_decode = (by, len(by_name[name]), int(k.group(1)))
    return run._scopes_decode


def decode_ms(run, wanted, per_token):
    got = full_dispatch_by_scope(run)
    if got is None:
        return None
    by, runs, k = got
    return sum(ns for (scope, _), ns in by.items() if scope in wanted
               ) / runs / (k if per_token else 1) / 1e6


def read(run):
    return decode_ms(run, DENSE, per_token=True)
