"""End-to-end sequence-parallel causal-LM training.

NEW capability (absent in the reference — SURVEY §2.14/§5: sequence/context
parallelism is listed "absent ... TPU-native equivalent to design fresh").
`ring_attention.py` / `ulysses.py` provide the attention op; this module is
the full training step built around it:

* the pure-functional transformer LM of `models/functional_lm.py` (params =
  plain pytree), whose position-wise ops (embed, layernorm, MLP, logits)
  shard trivially over the ``seq`` mesh axis via sharding constraints, and
  whose attention runs as a `shard_map` island using ring attention
  (ppermute K/V ring, flash-kernel partials) or Ulysses (all-to-all head
  sharding);
* `build_seq_parallel_train_step` — one jitted step (loss, grads, SGD
  update) over token batches sharded [B, T/P]; gradients flow through the
  custom ring/flash VJPs, so the whole thing trains on hardware.

Every device holds the full parameter pytree (replicated — combine with the
`sharding.py` fsdp/tp rules over extra mesh axes for larger models); what is
sharded is the SEQUENCE: activations never materialize the full [B, T]
context on one device, which is the point of context parallelism.
"""

from __future__ import annotations

from functools import partial

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..constants import AXIS_SEQ
from ..models.functional_lm import lm_loss
from .ring_attention import reference_attention, ring_attention
from .ulysses import ulysses_attention


def build_seq_parallel_train_step(mesh: Mesh, heads: int,
                                  strategy: str = "ring",
                                  learning_rate: float = 0.1,
                                  axis_name: str = AXIS_SEQ,
                                  remat: bool = False):
    """Returns (train_step, token_sharding): ``train_step(params, tokens)``
    → (new_params, loss), jitted over ``mesh`` with tokens sharded [B, T/P]
    and replicated params.  ``strategy``: "ring" | "ulysses" | "full"
    (full = no sequence parallelism, for parity checks); ``remat``
    rematerializes per-block activations for long-context memory."""
    spec = P(None, None, axis_name, None)

    if strategy == "full":
        attn_fn = partial(reference_attention, causal=True)
    else:
        if strategy not in ("ring", "ulysses"):
            raise ValueError(f"unknown strategy {strategy!r}; "
                             f"known: ring, ulysses, full")
        inner = ring_attention if strategy == "ring" else ulysses_attention

        @partial(jax.shard_map, mesh=mesh, in_specs=(spec, spec, spec),
                 out_specs=spec, check_vma=False)
        def attn_fn(q, k, v):
            return inner(q, k, v, axis_name=axis_name, causal=True)

    def train_step(params, tokens):
        loss, grads = jax.value_and_grad(lm_loss)(
            params, tokens, heads, attn_fn, remat)
        new_params = jax.tree_util.tree_map(
            lambda p, g: p - learning_rate * g, params, grads)
        return new_params, loss

    token_sharding = NamedSharding(mesh, P(None, axis_name))
    replicated = NamedSharding(mesh, P())
    step = jax.jit(train_step,
                   in_shardings=(replicated, token_sharding),
                   out_shardings=(replicated, replicated))
    return step, token_sharding
