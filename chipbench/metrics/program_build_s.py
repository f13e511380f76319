"""Seconds the process spent building programs (tracing, lowering, compiling,
fetching from the persistent cache): the program's
``fedml_program_build_seconds_total``, all stages summed, at the end of the
run."""

from chipbench.metrics.setup_cache_misses import counter_children


def read(run):
    stages = counter_children("fedml_program_build_seconds_total")
    return None if stages is None else sum(stages.values())
