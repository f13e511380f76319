"""The rows the expert layers' passes went over, for each pick that landed on
an expert this chip holds: the program's ``fedml_moe_rows_passed_total``
(whole chunks of the sorted, tile-padded layout, up to the last that holds a
landed row; summed over layers and steps in the epoch program and fetched
with the loss) over ``fedml_moe_picks_held_total``.  1 would be a pass over
the landed rows alone; a layout passed over whole for the worst case reads
(tiles + 1) x 256 rows a layer over the picks it landed.  A program that
keeps no such counter reports nothing."""

from chipbench.metrics.moe_picks_held_pct import counted, picks


def read(run):
    got, passed = picks(), counted("fedml_moe_rows_passed_total")
    if got is None or not got[1] or passed is None:
        return None
    return passed / got[1]
