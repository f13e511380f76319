"""The share of expert picks that landed on an expert this chip holds: the
program's ``fedml_moe_picks_held_total`` over ``fedml_moe_picks_total``, both
summed in the epoch program and fetched with the loss.  25 when the router's
load is even over a quarter of the experts.  A program that keeps no such
counters reports nothing."""


def counted(name):
    """An unlabelled counter of the program's process registry; nothing
    where the program keeps none of that name."""
    from fedml_tpu.core.mlops import metrics

    m = metrics.REGISTRY.collect().get(name)
    if m is None:
        return None
    return sum(child.value for child in m.children().values())


def picks():
    """(all picks, those that landed here), or nothing."""
    total = counted("fedml_moe_picks_total")
    held = counted("fedml_moe_picks_held_total")
    return (total, held) if total and held is not None else None


def read(run):
    got = picks()
    return None if got is None else 100.0 * got[1] / got[0]
