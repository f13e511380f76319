"""Pretrained-weight import for the functional LM plane.

Capability parity: the reference fine-tunes real HF checkpoints
(`/root/reference/python/fedml/train/llm/train_utils.py:196-244`,
AutoModelForCausalLM.from_pretrained).  TPU-native equivalent: map an
on-disk checkpoint (npz or safetensors) onto the functional-LM parameter
pytree (`models/functional_lm.init_lm_params` layout) with a full
shape/name REPORT, so train/llm fine-tuning and KV-cache serving start
from real weights instead of random init.

Supported schemas:
* ``native``  — the flat `export_lm_weights` naming (`embed`, `pos`,
  `ln_f.scale`, `blocks.{i}.wq`, ...): exact round-trip.
* ``gpt2``    — HF GPT-2 naming (`wte.weight`, `h.{i}.attn.c_attn.*`,
  ...).  GPT-2's Conv1D stores [in, out], matching our x @ W convention
  directly; fused c_attn splits into wq/wk/wv (+ biases).  Verified
  logit-equivalent against transformers' GPT2LMHeadModel in
  tests/test_weight_import.py.
* ``auto``    — sniff: GPT-2 markers → gpt2, else native.

Readers: `.npz` via numpy; `.safetensors` via the safetensors lib when
importable, else a dependency-free stdlib parser (the format is an
8-byte little-endian header length + JSON header + raw buffer).
"""

from __future__ import annotations

import json
import os
import struct
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

__all__ = [
    "read_checkpoint",
    "validate_lm_shapes",
    "export_lm_weights",
    "save_lm_checkpoint",
    "import_lm_weights",
    "load_pretrained_into",
]

_SAFETENSORS_DTYPES = {
    "F64": np.float64, "F32": np.float32, "F16": np.float16,
    "BF16": None,  # handled specially below
    "I64": np.int64, "I32": np.int32, "I16": np.int16, "I8": np.int8,
    "U8": np.uint8, "BOOL": np.bool_,
}


def _read_safetensors(path: str) -> Dict[str, np.ndarray]:
    try:
        from safetensors.numpy import load_file  # type: ignore

        return dict(load_file(path))
    except Exception:  # noqa: BLE001 — fall through to the stdlib parser
        pass
    with open(path, "rb") as f:
        (hlen,) = struct.unpack("<Q", f.read(8))
        header = json.loads(f.read(hlen).decode("utf-8"))
        buf = f.read()
    out: Dict[str, np.ndarray] = {}
    for name, meta in header.items():
        if name == "__metadata__":
            continue
        start, end = meta["data_offsets"]
        raw = buf[start:end]
        dt = meta["dtype"]
        if dt == "BF16":
            # widen bf16 → f32 via bit manipulation (numpy has no bf16)
            u16 = np.frombuffer(raw, np.uint16)
            arr = (u16.astype(np.uint32) << 16).view(np.float32)
        else:
            arr = np.frombuffer(raw, _SAFETENSORS_DTYPES[dt])
        out[name] = arr.reshape(meta["shape"]).copy()
    return out


def read_checkpoint(path: str) -> Dict[str, np.ndarray]:
    """Flat name → array dict from .npz or .safetensors."""
    if path.endswith(".safetensors"):
        return _read_safetensors(path)
    with np.load(path, allow_pickle=False) as z:
        return {k: np.asarray(z[k]) for k in z.files}


# ---------------------------------------------------------------- native
def export_lm_weights(params: Dict[str, Any]) -> Dict[str, np.ndarray]:
    """Functional-LM pytree → flat native-named array dict."""
    flat: Dict[str, np.ndarray] = {}

    def put(name, v):
        flat[name] = np.asarray(v)

    for key in ("embed", "pos", "w_out"):
        if key in params:
            put(key, params[key])
    for key in ("scale", "bias"):
        put(f"ln_f.{key}", params["ln_f"][key])
    for i, blk in enumerate(params["blocks"]):
        for key, v in blk.items():
            if isinstance(v, dict):           # ln1 / ln2
                for sub, vv in v.items():
                    put(f"blocks.{i}.{key}.{sub}", vv)
            else:
                put(f"blocks.{i}.{key}", v)
    return flat


def save_lm_checkpoint(params: Dict[str, Any], path: str) -> None:
    np.savez(path, **export_lm_weights(params))


def _import_native(state: Dict[str, np.ndarray]):
    params: Dict[str, Any] = {"blocks": [], "ln_f": {}}
    report = {"mapped": [], "unused": [], "missing": []}
    n_blocks = 1 + max((int(k.split(".")[1]) for k in state
                        if k.startswith("blocks.")), default=-1)
    params["blocks"] = [dict() for _ in range(n_blocks)]
    for name, arr in state.items():
        parts = name.split(".")
        if name in ("embed", "pos", "w_out"):
            params[name] = arr
        elif parts[0] == "ln_f" and len(parts) == 2:
            params["ln_f"][parts[1]] = arr
        elif parts[0] == "blocks" and len(parts) in (3, 4):
            blk = params["blocks"][int(parts[1])]
            if len(parts) == 4:
                blk.setdefault(parts[2], {})[parts[3]] = arr
            else:
                blk[parts[2]] = arr
        else:
            report["unused"].append(name)
            continue
        report["mapped"].append((name, name, list(arr.shape)))
    for req in ("embed", "pos"):
        if req not in params:
            report["missing"].append(req)
    for key in ("scale", "bias"):
        if key not in params["ln_f"]:
            report["missing"].append(f"ln_f.{key}")
    for i, blk in enumerate(params["blocks"]):
        for req in ("ln1", "wq", "wk", "wv", "wo", "ln2", "w1", "w2"):
            if req not in blk:
                report["missing"].append(f"blocks.{i}.{req}")
    return params, report


# ----------------------------------------------------------------- gpt2
def _import_gpt2(state: Dict[str, np.ndarray]):
    """HF GPT-2 state dict (torch .state_dict() names, with or without the
    `transformer.` prefix) → functional-LM pytree."""
    s = {k[len("transformer."):] if k.startswith("transformer.") else k: v
         for k, v in state.items()}
    report = {"mapped": [], "unused": [], "missing": [],
              "optional_absent": []}
    used = set()

    def take(name):
        if name in s:
            used.add(name)
            return np.asarray(s[name])
        report["missing"].append(name)
        return None

    def take_optional(name):
        """Biases are OPTIONAL in the functional LM (native init is
        bias-free); their absence is recorded but never fails strict."""
        if name in s:
            used.add(name)
            return np.asarray(s[name])
        report["optional_absent"].append(name)
        return None

    def put(dst, src_name, arr):
        report["mapped"].append((src_name, dst, list(arr.shape)))
        return arr

    params: Dict[str, Any] = {"blocks": []}
    wte = take("wte.weight")
    wpe = take("wpe.weight")
    if wte is None or wpe is None:
        return params, report
    params["embed"] = put("embed", "wte.weight", wte)
    params["pos"] = put("pos", "wpe.weight", wpe)
    n = 1 + max((int(k.split(".")[1]) for k in s if k.startswith("h.")),
                default=-1)
    dim = wte.shape[1]
    for i in range(n):
        blk: Dict[str, Any] = {}
        for ours, theirs in (("ln1", f"h.{i}.ln_1"), ("ln2", f"h.{i}.ln_2")):
            g, b = take(f"{theirs}.weight"), take(f"{theirs}.bias")
            if g is not None and b is not None:
                blk[ours] = {
                    "scale": put(f"blocks.{i}.{ours}.scale",
                                 f"{theirs}.weight", g),
                    "bias": put(f"blocks.{i}.{ours}.bias",
                                f"{theirs}.bias", b)}
        ca_w = take(f"h.{i}.attn.c_attn.weight")   # Conv1D: [in, 3*dim]
        ca_b = take_optional(f"h.{i}.attn.c_attn.bias")
        if ca_w is not None:
            for j, nm in enumerate(("wq", "wk", "wv")):
                blk[nm] = put(f"blocks.{i}.{nm}",
                              f"h.{i}.attn.c_attn.weight",
                              ca_w[:, j * dim:(j + 1) * dim])
            if ca_b is not None:
                for j, nm in enumerate(("bq", "bk", "bv")):
                    blk[nm] = put(f"blocks.{i}.{nm}",
                                  f"h.{i}.attn.c_attn.bias",
                                  ca_b[j * dim:(j + 1) * dim])
        for ours, theirs in (("wo", f"h.{i}.attn.c_proj"),
                             ("w1", f"h.{i}.mlp.c_fc"),
                             ("w2", f"h.{i}.mlp.c_proj")):
            w = take(f"{theirs}.weight")
            if w is not None:
                blk[ours] = put(f"blocks.{i}.{ours}", f"{theirs}.weight", w)
            b = take_optional(f"{theirs}.bias")
            if b is not None:
                bkey = {"wo": "bo", "w1": "b1", "w2": "b2"}[ours]
                blk[bkey] = put(f"blocks.{i}.{bkey}", f"{theirs}.bias", b)
        params["blocks"].append(blk)
    g, b = take("ln_f.weight"), take("ln_f.bias")
    if g is not None and b is not None:
        params["ln_f"] = {"scale": put("ln_f.scale", "ln_f.weight", g),
                          "bias": put("ln_f.bias", "ln_f.bias", b)}
    if "lm_head.weight" in s:
        # untied output head (torch Linear: [V, D] → transpose to [D, V]);
        # GPT-2 proper ties lm_head to wte, in which case skip
        head = np.asarray(s["lm_head.weight"])
        used.add("lm_head.weight")
        if not np.shares_memory(head, wte) and not np.array_equal(head, wte):
            params["w_out"] = put("w_out", "lm_head.weight", head.T)
    report["unused"] = sorted(set(s) - used - {"lm_head.weight"})
    # attention bias buffers (causal masks) are structural, not weights
    report["unused"] = [u for u in report["unused"]
                        if not u.endswith(".attn.bias")
                        and not u.endswith(".attn.masked_bias")]
    return params, report


def _sniff_schema(state: Dict[str, np.ndarray]) -> str:
    keys = set(state)
    if any(k.startswith(("wte.", "transformer.wte.")) for k in keys):
        return "gpt2"
    return "native"


def import_lm_weights(src: Any, schema: str = "auto", strict: bool = True,
                      dtype: Optional[Any] = None
                      ) -> Tuple[Dict[str, Any], Dict[str, List]]:
    """Checkpoint (path or flat dict) → (functional-LM pytree, report).

    ``report`` = {"mapped": [(src, dst, shape)], "missing": [...],
    "unused": [...]}.  ``strict`` raises on any missing parameter."""
    state = read_checkpoint(src) if isinstance(src, str) else dict(src)
    if schema == "auto":
        schema = _sniff_schema(state)
    if schema == "gpt2":
        params, report = _import_gpt2(state)
    elif schema == "native":
        params, report = _import_native(state)
    else:
        raise ValueError(f"unknown checkpoint schema {schema!r}; "
                         f"known: auto, native, gpt2")
    if strict and report["missing"]:
        raise ValueError(
            f"checkpoint is missing {len(report['missing'])} required "
            f"parameters: {report['missing'][:8]}...")
    # Core tensors are mandatory even under strict=False: a pytree without
    # the embeddings can never run, and letting it through produces a
    # far-away KeyError in validate_lm_shapes instead of a usable message.
    # Non-strict only forgives optional/per-block tensors.
    core_absent = [k for k in ("embed", "pos") if k not in params]
    if core_absent:
        raise ValueError(
            f"checkpoint is unusable: core tensors {core_absent} are absent "
            f"(schema={schema!r}); strict=False only relaxes optional/extra "
            f"tensors, not the embeddings")
    import jax.numpy as jnp

    cast = (lambda a: jnp.asarray(a, dtype)) if dtype is not None \
        else jnp.asarray
    params = __import__("jax").tree_util.tree_map(cast, params)
    return params, report


def validate_lm_shapes(params: Dict[str, Any], vocab: Optional[int] = None,
                       dim: Optional[int] = None,
                       heads: Optional[int] = None,
                       min_len: Optional[int] = None) -> None:
    """Fail LOUDLY on checkpoint/config mismatches that JAX would
    otherwise absorb silently (out-of-bounds embedding gathers clamp
    under jit; a wrong head count still reshapes cleanly and just
    computes garbage attention groupings)."""
    v, d = params["embed"].shape
    problems = []
    if vocab is not None and int(vocab) != int(v):
        problems.append(f"vocab: checkpoint {v} vs config {vocab}")
    if dim is not None and int(dim) != int(d):
        problems.append(f"dim: checkpoint {d} vs config {dim}")
    if heads is not None and int(d) % int(heads) != 0:
        problems.append(f"heads: config {heads} does not divide "
                        f"checkpoint dim {d}")
    if min_len is not None and params["pos"].shape[0] < int(min_len):
        problems.append(f"max_len: checkpoint has {params['pos'].shape[0]} "
                        f"positions < config {min_len}")
    if problems:
        raise ValueError("pretrained checkpoint does not match the model "
                         "config: " + "; ".join(problems))


def load_pretrained_into(variables: Dict[str, Any], path: str,
                         schema: str = "auto", strict: bool = True,
                         module: Any = None
                         ) -> Tuple[Dict[str, Any], Dict[str, List]]:
    """Replace ``variables['params']`` with imported weights (the
    `train/llm` + serving entry point).  When ``module`` (a
    FunctionalLMModule-like object with vocab/dim/heads/max_len) is
    given, the checkpoint dims are VALIDATED against it."""
    params, report = import_lm_weights(path, schema=schema, strict=strict)
    if module is not None:
        validate_lm_shapes(
            params,
            vocab=getattr(module, "vocab", None),
            dim=getattr(module, "dim", None),
            heads=getattr(module, "heads", None),
            min_len=None)
    return dict(variables, params=params), report
