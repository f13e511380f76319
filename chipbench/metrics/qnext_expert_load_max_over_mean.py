"""How uneven the 128 held experts' load is: the picks of the heaviest held
expert of a step (any layer; the program's ``fedml_moe_expert_picks_max``,
summed over steps) over the mean picks of a held expert of a layer in a step
(``moe_expert_load_max_over_mean``'s reading, from this configuration's
keys).  1 when every held expert of every layer gets the same."""

from chipbench.metrics.moe_picks_held_pct import counted, picks


def read(run):
    got, heaviest = picks(), counted("fedml_moe_expert_picks_max")
    if got is None or not heaviest or not got[1]:
        return None
    cfg = run.config
    return heaviest * int(cfg["num_hidden_layers"]) * int(
        cfg["num_experts"]) / got[1]
