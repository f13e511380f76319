"""Host milliseconds of an engine iteration during which the device has
nothing new to do: the summed lengths of the program's ``fedml.serve.build``,
``fedml.serve.dispatch.k<k>`` and ``fedml.serve.stream`` spans over the number
of dispatches in the trace (the wait for the device, ``fedml.serve.fetch``, is
not host work)."""

DISPATCH = "fedml.serve.dispatch.k"
HOST_WORK = ("fedml.serve.build", "fedml.serve.stream")


def dispatches(run):
    """The engine's dispatch spans in the trace: nothing where the run was
    not traced or the program opens none."""
    if run.trace is None:
        return []
    return [h for h in run.trace.host if h.name.startswith(DISPATCH)]


def read(run):
    found = dispatches(run)
    if not found:
        return None
    ns = sum(h.dur for h in found) + sum(
        h.dur for h in run.trace.host if h.name in HOST_WORK)
    return ns / len(found) / 1e6
