"""The share of expert picks that landed on an expert this chip holds, under
the group-limited router: the program's ``fedml_moe_picks_held_total`` over
``fedml_moe_picks_total`` (``moe_picks_held_pct``'s reading, of another
cell).  3.125 when the load is even over 8 of 256 experts."""

from chipbench.metrics.moe_picks_held_pct import read  # noqa: F401
