"""LoRA for flax param pytrees.

Capability parity: reference `train/llm/configurations.py:161-324` (PEFT/LoRA
config) — but implemented functionally: LoRA is a TRANSFORM on the param
pytree, not a model wrapper.  ``init_lora`` allocates (A, B) factors for every
kernel matching the target patterns; ``apply_lora`` returns effective params
W + (alpha/r)·(A@B); training optimizes only the LoRA leaves, which composes
with any jitted loss because everything is pure tree math.
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from ...core.mlops import tracing

DEFAULT_TARGETS = (r".*attention.*kernel", r".*(query|key|value|out).*kernel",
                   r".*Dense_\d+.*kernel",
                   # functional-LM layout (models/functional_lm.py):
                   # per-block attention/MLP matmuls; a latent layer's two
                   # matrices each for q and for keys and values; a
                   # delta-rule layer's projection into q, k, v and its gate
                   # (its way out is a ``wo`` like any other)
                   r".*/w[qkvo]", r".*/w[12]", r".*/w(q|kv)_[ab]",
                   r".*/w_qkvz")


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def _is_target(path: str, shape, targets: Sequence[str]) -> bool:
    if len(shape) != 2:
        return False
    return any(re.fullmatch(t, path, flags=re.IGNORECASE) for t in targets)


def init_lora(params: Any, rank: int = 8, targets: Sequence[str] = None,
              rng: jax.Array = None, dtype=jnp.float32) -> Dict[str, Any]:
    """→ {path: {"a": [d_in, r], "b": [r, d_out]}} for each targeted kernel.

    ``rng`` should be a dedicated split of the caller's key (LLMTrainer
    threads one through) so the factors never correlate with the base-param
    init; the PRNGKey(0) fallback is for standalone deterministic use only.
    """
    targets = tuple(targets or DEFAULT_TARGETS)
    if rng is None:
        rng = jax.random.PRNGKey(0)
    lora: Dict[str, Any] = {}
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    for i, (path, leaf) in enumerate(flat):
        p = _path_str(path)
        if _is_target(p, jnp.shape(leaf), targets):
            k = jax.random.fold_in(rng, i)
            d_in, d_out = leaf.shape
            lora[p] = {
                "a": (jax.random.normal(k, (d_in, rank)) * 0.01).astype(dtype),
                "b": jnp.zeros((rank, d_out), dtype),
            }
    return lora


@tracing.scope("lora")
def apply_lora(params: Any, lora: Dict[str, Any], alpha: float = 16.0
               ) -> Any:
    """Effective params: W' = W + (alpha/r)·A@B for targeted kernels.  A
    kernel kept below the factors' type gets the sum formed in theirs and
    rounded once, elementwise: no copy of it in the higher type is made."""
    if not lora:
        return params
    some = next(iter(lora.values()))
    scale = alpha / some["a"].shape[1]

    def update(path, leaf):
        p = _path_str(path)
        if p in lora:
            ab = lora[p]["a"] @ lora[p]["b"]
            if jnp.promote_types(leaf.dtype, ab.dtype) == leaf.dtype:
                return leaf + scale * ab.astype(leaf.dtype)
            return (leaf.astype(ab.dtype) + scale * ab).astype(leaf.dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(update, params)


def merge_lora(params: Any, lora: Dict[str, Any], alpha: float = 16.0) -> Any:
    """Bake LoRA into the base weights (for serving/export)."""
    return apply_lora(params, lora, alpha)


def count_trainable(lora: Dict[str, Any]) -> int:
    return sum(int(jnp.size(v)) for d in lora.values() for v in d.values())
