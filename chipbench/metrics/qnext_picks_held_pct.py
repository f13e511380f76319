"""The share of expert picks that landed on an expert this chip holds, of 10
picks a token among 512: the program's ``fedml_moe_picks_held_total`` over
``fedml_moe_picks_total`` (``moe_picks_held_pct``'s reading, of another cell).
25 when the load is even over 128 of 512 experts."""

from chipbench.metrics.moe_picks_held_pct import read  # noqa: F401
