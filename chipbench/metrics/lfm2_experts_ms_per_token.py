"""Device milliseconds a token step of the full ``decode_multi`` dispatch
spends on the routed layers of the LFM2 stage: the scopes ``fedml.router``
and ``fedml.experts.*`` (the plan, the layout, the two products' kernels, the
combine) inside the full dispatch's executions, over those executions and the
``k`` of the program's name."""

from chipbench.metrics.decode_dense_ms_per_token import full_dispatch_by_scope


def under(by, wanted):
    """Nanoseconds of the scopes of ``by`` (`scopes.time_by_scope`'s) that
    are one of ``wanted`` or lie under one."""
    return sum(ns for (scope, _), ns in by.items()
               if any(scope == w or scope.startswith(w + ".")
                      for w in wanted))


def scopes_ms(run, wanted, per_token=True):
    """Milliseconds of the scopes that are one of ``wanted`` or lie under
    one (``fedml.experts`` takes ``fedml.experts.plan``), an execution of the
    full dispatch, a token step where ``per_token``."""
    got = full_dispatch_by_scope(run)
    if got is None:
        return None
    by, runs, k = got
    return under(by, wanted) / runs / (k if per_token else 1) / 1e6


def read(run):
    return scopes_ms(run, ("fedml.router", "fedml.experts"))
