"""The instrument's own health: per cent of the epoch program's device self
time that lands on no ``fedml.*`` scope: an instruction the program wrote
under none (``(unscoped)``), one of the compiler's own (``(no op_name)``: a
copy or a slice it put in), or an event whose instruction the trace's HLO does
not hold (``(not in the map)``).  Over the ``train()`` calls the trace holds
whole."""

from chipbench.harness import scopes


def read(run):
    lost = scopes.epoch_ms_per_step(run, scopes.LOST)
    return None if lost is None else 100.0 * lost / scopes.epoch_ms_per_step(
        run)
