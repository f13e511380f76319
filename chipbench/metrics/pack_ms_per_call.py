"""Host milliseconds ``LLMTrainer.train()`` spends packing the token stream
and copying the batches to the device (the program's ``fedml.sft.pack`` span),
median over the calls the trace holds whole."""

from chipbench.metrics.opt_init_ms_per_call import span_ms_per_call


def read(run):
    return span_ms_per_call(run, "fedml.sft.pack")
