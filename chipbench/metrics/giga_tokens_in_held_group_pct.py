"""The share of (token, routed block) pairs whose kept groups of experts
include the group this chip's experts lie in: the program's
``fedml_moe_tokens_in_held_group_total`` over ``fedml_moe_picks_total`` /
top-k.  50 when 4 of 8 groups are kept evenly; only these tokens can land a
pick here.  A program that keeps no such counter reports nothing."""

from chipbench.metrics.moe_picks_held_pct import counted


def read(run):
    kept = counted("fedml_moe_tokens_in_held_group_total")
    total = counted("fedml_moe_picks_total")
    if kept is None or not total:
        return None
    return 100.0 * kept * run.config["num_experts_per_tok"] / total
