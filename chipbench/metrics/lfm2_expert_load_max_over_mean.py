"""How uneven the experts' load is at decode in the LFM2 stage: the picks of
the heaviest expert of a token step (any layer; the program's
``fedml_moe_expert_picks_max``) over the mean picks of an expert of a layer
in a token step.  1 when every expert of every layer gets the same."""

from chipbench.kernels import lfm2_decode
from chipbench.metrics.lfm2_decode_roofline import per_token_step


def read(run):
    mean = per_token_step(run)
    if mean is None or not mean["picks"]:
        return None
    return mean["picks_max"] * lfm2_decode.routed_layers(run.config) * int(
        run.config["num_experts"]) / mean["picks"]
