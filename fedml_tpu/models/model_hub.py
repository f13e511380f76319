"""Model hub — ``create(args, output_dim)`` dispatch.

Capability parity: reference `model/model_hub.py:19-90` (lr, cnn,
resnet18_gn, rnn, resnet56/resnet20, mobilenet, mobilenet_v3, efficientnet,
darts, gan, mnn-mobile).  Returns a ``ModelBundle`` wrapping the flax module
plus task/shape metadata the engine needs.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax.numpy as jnp

from ..ml.engine.model_bundle import (
    TASK_BINARY,
    TASK_CLASSIFICATION,
    TASK_LM,
    ModelBundle,
)
from .cv import (
    CIFARCNN,
    CIFARResNet,
    EfficientNetB0,
    CNNDropOut,
    FedAvgCNN,
    LogisticRegression,
    MobileNetV1,
    MobileNetV3Small,
    ResNet18,
)
from .cv import LeNet5, UNetLite, VGG
from .darts import DARTSNetwork, DARTSSearchNetwork
from .finance import TabularMLP, VFLBottomModel
from .gan import DCGANDiscriminator
from .nlp import CharLSTM, StackOverflowLSTM, TinyTransformerLM, ViT

# dataset → (input_shape, default_classes, task)
_DATASET_SHAPES = {
    "mnist": ((28, 28, 1), 10, TASK_CLASSIFICATION),
    "femnist": ((28, 28, 1), 62, TASK_CLASSIFICATION),
    "synthetic": ((60,), 10, TASK_CLASSIFICATION),
    "cifar10": ((32, 32, 3), 10, TASK_CLASSIFICATION),
    "cifar100": ((32, 32, 3), 100, TASK_CLASSIFICATION),
    "fed_cifar100": ((32, 32, 3), 100, TASK_CLASSIFICATION),
    "cinic10": ((32, 32, 3), 10, TASK_CLASSIFICATION),
    "shakespeare": ((80,), 90, TASK_LM),
    "fed_shakespeare": ((80,), 90, TASK_LM),
    "stackoverflow_nwp": ((20,), 10004, TASK_LM),
    "stackoverflow_lr": ((10004,), 500, TASK_CLASSIFICATION),
    "adult": ((105,), 2, TASK_BINARY),
    "ilsvrc2012": ((224, 224, 3), 1000, TASK_CLASSIFICATION),
    "imagenet": ((224, 224, 3), 1000, TASK_CLASSIFICATION),
    "synthetic_seg": ((24, 24, 3), 4, TASK_CLASSIFICATION),
    "gld23k": ((96, 96, 3), 203, TASK_CLASSIFICATION),
    "gld160k": ((96, 96, 3), 2028, TASK_CLASSIFICATION),
    "fets2021": ((32, 32, 3), 4, TASK_CLASSIFICATION),
    "autonomous_driving": ((32, 32, 3), 4, TASK_CLASSIFICATION),
    "uci": ((105,), 2, TASK_BINARY),
    "uci_adult": ((105,), 2, TASK_BINARY),
    "reddit": ((20,), 10000, TASK_LM),
    "fednlp": ((5000,), 20, TASK_CLASSIFICATION),
    "20news": ((5000,), 20, TASK_CLASSIFICATION),
    "agnews": ((5000,), 20, TASK_CLASSIFICATION),
    "nus_wide": ((1634,), 5, TASK_CLASSIFICATION),
    "nus-wide": ((1634,), 5, TASK_CLASSIFICATION),
    "lending_club_loan": ((90,), 2, TASK_BINARY),
    "lending_club": ((90,), 2, TASK_BINARY),
}


def dataset_meta(dataset: str) -> Tuple[Tuple[int, ...], int, str]:
    name = str(dataset).lower()
    # poisoned variants share the base dataset's contract (data/datasets.py)
    name = name.replace("edge_case_", "").replace("_poisoned", "") or name
    if name.startswith("synthetic_") and name not in _DATASET_SHAPES:
        # LEAF SYNTHETIC(α,β) variants share the base synthetic contract
        return _DATASET_SHAPES["synthetic"]
    return _DATASET_SHAPES.get(name, ((32, 32, 3), 10, TASK_CLASSIFICATION))


def create(args: Any, output_dim: Optional[int] = None) -> ModelBundle:
    name = str(getattr(args, "model", "lr")).lower()
    dataset = str(getattr(args, "dataset", "mnist")).lower()
    input_shape, default_dim, task = dataset_meta(dataset)
    num_classes = int(output_dim or default_dim)
    dtype = jnp.bfloat16 if str(
        getattr(args, "compute_dtype", "bfloat16")) == "bfloat16" else jnp.float32
    input_dtype = (jnp.int32 if task == TASK_LM else jnp.float32)

    if name == "lr":
        module = LogisticRegression(
            num_classes, dtype=dtype,
            sigmoid_output=bool(getattr(args, "lr_sigmoid_outputs", False)))
        if task == TASK_LM:  # lr on text = bag-of-words; keep classification
            task = TASK_CLASSIFICATION
    elif name == "cnn":
        if len(input_shape) >= 3 and input_shape[-1] == 3:
            module = CIFARCNN(num_classes, dtype=dtype)
        else:
            module = FedAvgCNN(num_classes, dtype=dtype)
    elif name == "cnn_dropout":
        # reference `model_hub.py:32-37`: mnist/femnist "cnn" builds
        # CNN_DropOut(only_digits=False) — 62 heads even on mnist; exact
        # arch for the conv parity audit, dropout rates overridable
        # (parity zeroes them: dropout RNG is framework-specific)
        r1, r2 = (getattr(args, "cnn_dropout_rates", None)
                  or (0.25, 0.5))
        module = CNNDropOut(num_classes=62, rate1=float(r1),
                            rate2=float(r2), dtype=dtype)
    elif name in ("resnet56", "resnet20", "resnet32"):
        depth = int(name.replace("resnet", ""))
        module = CIFARResNet(
            depth=depth, num_classes=num_classes, dtype=dtype,
            norm=str(getattr(args, "norm", "bn")),
            conv_impl=str(getattr(args, "conv_impl", "lax") or "lax"))
    elif name in ("resnet18", "resnet18_gn"):
        module = ResNet18(num_classes=num_classes, dtype=dtype,
                          norm="gn" if name.endswith("gn") else "bn")
    elif name == "mobilenet":
        module = MobileNetV1(num_classes=num_classes, dtype=dtype)
    elif name == "mobilenet_v3":
        module = MobileNetV3Small(num_classes=num_classes, dtype=dtype)
    elif name == "efficientnet":
        module = EfficientNetB0(num_classes=num_classes, dtype=dtype)
    elif name == "rnn":
        if dataset.startswith("stackoverflow"):
            module = StackOverflowLSTM(vocab_size=num_classes, dtype=dtype)
        else:
            module = CharLSTM(vocab_size=num_classes, dtype=dtype)
        task = TASK_LM
    elif name in ("transformer", "bert_tiny", "bert-tiny"):
        module = TinyTransformerLM(vocab_size=num_classes, dtype=dtype)
        task = TASK_LM
    elif name in ("functional_lm", "kv_lm"):
        # the pure-pytree LM (models/functional_lm.py) shared with the
        # sequence-parallel step and the KV-cache serving engine:
        # fine-tune it here (LoRA targets its wq/wk/wv/wo/w1/w2 matmuls),
        # then serve the SAME params through
        # serving/kv_cache_lm.KVCacheLM with zero conversion
        from .functional_lm import FunctionalLMModule

        module = FunctionalLMModule(
            vocab=num_classes,
            dim=int(getattr(args, "lm_dim", 64) or 64),
            layers=int(getattr(args, "lm_layers", 2) or 2),
            heads=int(getattr(args, "lm_heads", 4) or 4),
            max_len=int(getattr(args, "lm_max_len", 256) or 256))
        task = TASK_LM
    elif name == "routed_lm":
        # the same functional LM under another description: RMSNorm, an
        # untied head, routed experts of which this chip holds
        # `lm_experts_held` from `lm_first_held` on.  As given: grouped
        # heads, and two layouts that give a layer 1 where it rotates q and
        # k / looks back `lm_window` positions only, ReGLU experts behind a
        # softmax router that reads the block's input.  With `lm_latent` (a
        # `functional_lm.Latent`'s fields): latent attention in every layer;
        # with `lm_router` (`scores groups kept_groups scale act reads` of
        # `routed_experts.Experts`): another router or activation; with
        # `lm_dense_layers` / `lm_dense_ffn`: leading dense SwiGLU layers;
        # `lm_shared_ffn`: a shared expert in the routed ones; `lm_mtp` /
        # `lm_mtp_weight`: a second head one token further; `lm_store`: the
        # type the frozen matrices are kept in.  With `lm_delta` (a
        # `functional_lm.DeltaRule`'s fields) and `lm_delta_layout`: a gated
        # delta rule in place of attention where the layout gives a layer 1;
        # `lm_attention` (`rotary qk_norm out_gate` of `Layer`) shapes the
        # attention of the others; `lm_centred_norm`: norm scales of `1 + g`;
        # `lm_shared_gate`: the shared expert behind a sigmoid gate.  With
        # `lm_conv` (a `functional_lm.ShortConv`'s fields) and
        # `lm_conv_layout`: a gated short convolution in place of attention
        # where the layout gives a layer 1
        from ..ops.routed_experts import Experts
        from .functional_lm import (DeltaRule, Latent, Layer, RoutedLMModule,
                                    ShortConv)

        get = lambda key, default=None: getattr(args, key, default) or default
        experts = Experts(
            total=int(args.lm_experts), held=int(args.lm_experts_held),
            first_held=int(get("lm_first_held", 0)),
            top_k=int(args.lm_top_k), **dict(get("lm_router", {})))
        shape = dict(norm="rmsnorm", eps=float(args.lm_norm_eps),
                     centred=bool(get("lm_centred_norm", False)))
        if get("lm_latent"):
            shape["latent"] = Latent(**dict(args.lm_latent))
            attention = [shape] * int(args.lm_layers)
        else:
            shape.update(kv_heads=int(args.lm_kv_heads),
                         head_dim=int(args.lm_head_dim))
            shape.update(get("lm_attention", {}))
            attention = [dict(
                shape, rope_theta=float(args.lm_rope_theta) if rotates
                else None, window=int(args.lm_window) if windowed else None)
                for rotates, windowed in zip(args.lm_rope_layout,
                                             args.lm_window_layout)]
            if get("lm_delta"):
                delta = DeltaRule(**dict(args.lm_delta))
                attention = [dict(a, delta=delta) if recurrent else a
                             for a, recurrent in zip(attention,
                                                     args.lm_delta_layout)]
            if get("lm_conv"):
                conv = ShortConv(**dict(args.lm_conv))
                attention = [dict(a, conv=conv) if short else a
                             for a, short in zip(attention,
                                                 args.lm_conv_layout)]
        routed = dict(experts=experts, shared=get("lm_shared_ffn"),
                      shared_gate=bool(get("lm_shared_gate", False)))
        dense = int(get("lm_dense_layers", 0))
        module = RoutedLMModule(
            vocab=num_classes, dim=int(args.lm_dim),
            heads=int(args.lm_heads), ffn=int(args.lm_ffn),
            layers=[Layer(**a, **(dict(swiglu=int(args.lm_dense_ffn))
                                  if i < dense else routed))
                    for i, a in enumerate(attention)],
            mtp=Layer(**attention[-1], **routed) if get("lm_mtp") else None,
            mtp_weight=float(get("lm_mtp_weight", 0.0)),
            store=str(get("lm_store", "float32")))
        task = TASK_LM
    elif name in ("vit", "vit_tiny", "vit-tiny"):
        module = ViT(num_classes=num_classes, dtype=dtype,
                     layers=int(getattr(args, "vit_layers", 6)))
    elif name in ("vgg11", "vgg16", "vgg"):
        depth = 16 if name.endswith("16") else 11
        module = VGG(num_classes=num_classes, depth=depth, dtype=dtype,
                     norm=str(getattr(args, "norm", "bn")))
    elif name == "lenet":
        module = LeNet5(num_classes=num_classes, dtype=dtype)
    elif name in ("unet", "deeplab", "segmentation"):
        module = UNetLite(num_classes=num_classes, dtype=dtype)
    elif name in ("darts", "darts_search"):
        module = DARTSSearchNetwork(num_classes=num_classes, dtype=dtype)
    elif name in ("darts_train", "nas_train"):
        module = DARTSNetwork(num_classes=num_classes, dtype=dtype)
    elif name == "gan":
        # bundle wraps the discriminator (the federated-averaged part in
        # fedgan); the generator is built alongside by the fedgan algorithm
        module = DCGANDiscriminator(dtype=dtype)
        task = TASK_BINARY
    elif name in ("mlp", "tabular_mlp"):
        module = TabularMLP(num_classes=num_classes, dtype=dtype)
    elif name.startswith("vfl"):
        module = VFLBottomModel(dtype=dtype)
    else:
        raise ValueError(f"unknown model {name!r}")

    return ModelBundle(module=module, input_shape=input_shape,
                       num_classes=num_classes, task=task,
                       input_dtype=input_dtype, name=name)
