"""A full ``decode_multi`` dispatch of the LFM2 stage against the memory
roofline of the bytes its token steps cannot avoid
(``chipbench/kernels/lfm2_decode.py``): a token step the matrices outside the
experts once, the matrices of the experts its live rows touched and the live
blocks of the key/value cache, both as the program counted them (means over
the run's token steps), times the dispatch's token steps, over the HBM peak,
over the dispatch's median device time.  The cell's share of the whole step.
Nothing on a program that keeps no such counters."""

import re

from chipbench.harness import xplane
from chipbench.harness.stats import median
from chipbench.kernels import lfm2_decode
from chipbench.metrics.decode_device_ms import PROGRAM, variants_ns
from chipbench.metrics.moe_picks_held_pct import counted


def per_token_step(run):
    """Means over the run's token steps of what the program counted:
    ``{"touched", "picks", "picks_max", "live_blocks", "steps"}``; nothing
    where a counter is missing."""
    touched = counted("fedml_moe_experts_touched_total")
    picks = counted("fedml_moe_picks_total")
    heaviest = counted("fedml_moe_expert_picks_max")
    blocks = counted("fedml_llm_cache_blocks_total")
    live = counted("fedml_llm_cache_blocks_live_total")
    if not touched or not picks or not blocks or live is None:
        return None
    per_step = int(run.cell["traffic"]["max_batch"]) * -(
        -int(run.config["n_positions"]) // lfm2_decode.CACHE_BLOCK)
    steps = blocks / per_step
    return {"touched": touched / steps, "picks": picks / steps,
            "picks_max": (heaviest or 0.0) / steps,
            "live_blocks": live / steps, "steps": steps}


def full_dispatches(run):
    """(the executions of the full dispatch's program in the trace, its
    ``k``); nothing where the variants cannot be told apart."""
    found = variants_ns(run)
    if not found:
        return None
    by_name = {}
    for m in xplane.matching(xplane.first_device_modules(run.trace), PROGRAM):
        by_name.setdefault(m.name, []).append(m)
    name = next((n for n, ms in by_name.items()
                 if [m.dur for m in ms] == found[0]), None)
    k = re.search(r"_k(\d+)", name or "")
    return (by_name[name], int(k.group(1))) if k else None


def read(run):
    got, mean = full_dispatches(run), per_token_step(run)
    if got is None or mean is None:
        return None
    ran, k = got
    step = lfm2_decode.token_step_bytes(run.config, mean["touched"],
                                        mean["live_blocks"], 2)
    ns = median(m.dur for m in ran)
    run.rec.say("lfm2_decode_roofline", token_step_bytes=step,
                experts_touched_a_step=mean["touched"],
                live_blocks_a_step=mean["live_blocks"], k=k,
                dispatch_us=ns / 1e3)
    return 100.0 * k * step["total"] / run.peaks["hbm_bytes_per_s"] / (
        ns / 1e9)
