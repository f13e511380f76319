"""The share of the KV cache that ``decode_multi``'s attention had to read:
blocks of 128 positions of one row that held a position a query could attend
to, over all the blocks there are, both summed over the run's token steps
(the program's ``fedml_llm_cache_blocks_live_total`` over
``fedml_llm_cache_blocks_total``, counted on the host as each dispatch is
built).  A program that keeps no such counters reports nothing."""

from chipbench.metrics.setup_cache_misses import counter_children


def read(run):
    live = counter_children("fedml_llm_cache_blocks_live_total")
    total = counter_children("fedml_llm_cache_blocks_total")
    if not live or not total or not sum(total.values()):
        return None
    return 100.0 * sum(live.values()) / sum(total.values())
