"""The expert kernels' share of their roofline in the family with delta-rule
layers: the least time the chip could take for the six expert products of a
layer in a step over the picks that landed, each held expert's bfloat16 matrix
fetched once a product (``chipbench/kernels/qwen3next_train.py``; the picks as
the program counted them, a mean over layers and steps), over the kernels'
device time for them."""

from chipbench.kernels import qwen3next_train
from chipbench.metrics.moe_experts_ms_per_step import kernel_ns_and_steps
from chipbench.metrics.moe_picks_held_pct import picks


def read(run):
    got, counted = kernel_ns_and_steps(run), picks()
    if got is None or counted is None:
        return None
    total_ns, n_events, _ = got
    cfg, t = run.config, run.cell["traffic"]
    rows = (counted[1] / counted[0] * cfg["num_experts_per_tok"]
            * t["batch_size"] * t["seq_len"])
    least = qwen3next_train.experts_least_seconds(cfg, rows, 2, run.peaks)
    layer_steps = n_events / least["products"]
    run.rec.say("qnext_experts_roofline", rows_per_layer_step=rows,
                bound=least["bound"], least_us=least["seconds"] * 1e6,
                measured_us=total_ns / layer_steps / 1e3,
                kernel_events=n_events)
    return 100.0 * least["seconds"] * layer_steps / (total_ns / 1e9)
