"""The expert kernels' share of their roofline: the least time the chip could
take for the six expert products of a layer in a step over the picks that
landed (``chipbench/kernels/smallthinker_train.py``; the picks as the program
counted them, a mean over layers and steps), over the kernels' device time for
them."""

from chipbench.kernels import smallthinker_train
from chipbench.metrics.moe_experts_ms_per_step import kernel_ns_and_steps
from chipbench.metrics.moe_picks_held_pct import picks


def read(run):
    got, counted = kernel_ns_and_steps(run), picks()
    if got is None or counted is None:
        return None
    total_ns, n_events, _ = got
    cfg, t = run.config, run.cell["traffic"]
    landed_share = counted[1] / counted[0]
    rows = (landed_share * cfg["moe_num_active_primary_experts"]
            * t["batch_size"] * t["seq_len"])
    least = smallthinker_train.experts_least_seconds(cfg, rows, run.peaks)
    layer_steps = n_events / least["products"]
    run.rec.say("moe_experts_roofline", rows_per_layer_step=rows,
                least_us=least["seconds"] * 1e6,
                measured_us=total_ns / layer_steps / 1e3,
                kernel_events=n_events)
    return 100.0 * least["seconds"] * layer_steps / (total_ns / 1e9)
