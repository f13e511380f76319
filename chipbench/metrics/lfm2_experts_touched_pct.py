"""The share of the LFM2 stage's experts whose matrices a token step had to
fetch: the program's ``fedml_moe_experts_touched_total`` (experts a live row
picked at all, summed over layers and token steps) over the experts there
are, routed layers x ``num_experts`` a token step."""

from chipbench.kernels import lfm2_decode
from chipbench.metrics.lfm2_decode_roofline import per_token_step


def read(run):
    mean = per_token_step(run)
    if mean is None:
        return None
    return 100.0 * mean["touched"] / (
        lfm2_decode.routed_layers(run.config)
        * int(run.config["num_experts"]))
