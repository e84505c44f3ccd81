"""L3 model plug-in point.

The reference's contract is a user-editable ``model_fn() -> tf.keras.Model``
(reference initializer.py:12-21, README.md:12).  Here ``model_fn`` returns a
``flax.linen.Module`` whose ``__call__(x, train: bool)`` produces logits; the
registry gives named access for the CLI, and users can still pass their own
callable exactly like the reference.
"""

from __future__ import annotations

from typing import Callable

import flax.linen as nn
import jax.numpy as jnp

from distributed_tensorflow_tpu.models.mlp import MLP
from distributed_tensorflow_tpu.models.cnn import CNN

_REGISTRY: dict[str, Callable[..., nn.Module]] = {}

_DTYPES = {
    "float32": jnp.float32, "f32": jnp.float32, "fp32": jnp.float32,
    "bfloat16": jnp.bfloat16, "bf16": jnp.bfloat16,
    "float16": jnp.float16, "f16": jnp.float16, "fp16": jnp.float16,
}


def resolve_dtype(dtype) -> jnp.dtype:
    """Map a CLI string ('bfloat16', 'bf16', ...) or dtype to a jnp dtype.

    Mixed precision on TPU: models compute in ``dtype`` (bf16 feeds the MXU
    at full rate and halves HBM traffic for activations) while flax keeps
    parameters in float32 (``param_dtype`` default), so optimizer math and
    gradient accumulation stay full-precision.
    """
    if isinstance(dtype, str):
        key = dtype.lower()
        if key not in _DTYPES:
            raise KeyError(f"unknown dtype '{dtype}'; known: {sorted(_DTYPES)}")
        return _DTYPES[key]
    return dtype


def register(name: str):
    def deco(fn):
        _REGISTRY[name] = fn
        return fn

    return deco


@register("mlp")
@register("mnist_mlp")
def _mlp(num_classes: int = 10, **kw) -> nn.Module:
    """The reference's default model_fn: Flatten→Dense(512,relu)→Dropout(0.2)
    →Dense(10) (reference initializer.py:14-19)."""
    return MLP(num_classes=num_classes, **kw)


@register("cnn")
@register("mnist_cnn")
def _cnn(num_classes: int = 10, **kw) -> nn.Module:
    return CNN(num_classes=num_classes, **kw)


@register("fashion_mlp")
def _fashion_mlp(num_classes: int = 10, **kw) -> nn.Module:
    return MLP(num_classes=num_classes, **kw)


def create_model(name: str, num_classes: int = 10, **kw) -> nn.Module:
    """Instantiate a registered model (lazy imports keep startup light).

    Beside the registry's classifiers: ``resnet20``, ``bert_tiny``, ``moe``
    and the language models ``gpt`` (``models/gpt.GPTLM``), ``mla_moe``
    (latent attention, sparse experts), ``hybrid_ssm`` (state-space,
    attention and latent-expert mixers by a pattern), ``window_moe``
    (window and full attention by a pattern, a parallel block, averaged
    shared experts) and ``jamba`` (selective-scan state-space and
    multi-query attention mixers by a period, a dense SwiGLU after each, a
    tied head); the five serve through ``serving.SlotKVCache``."""
    if "dtype" in kw:
        kw["dtype"] = resolve_dtype(kw["dtype"])
    if name in ("resnet20", "resnet"):
        from distributed_tensorflow_tpu.models.resnet import ResNet20

        return ResNet20(num_classes=num_classes, **kw)
    if name in ("bert_tiny", "bert"):
        from distributed_tensorflow_tpu.models.bert import BertTinyClassifier

        return BertTinyClassifier(num_classes=num_classes, **kw)
    if name in ("moe", "moe_mlp"):
        from distributed_tensorflow_tpu.models.moe import MoEClassifier

        return MoEClassifier(num_classes=num_classes, **kw)
    if name in ("gpt", "gpt_tiny"):
        from distributed_tensorflow_tpu.models.gpt import GPTLM

        # an LM's "classes" are its tokens: the harness threads the
        # dataset's num_classes (= vocab size for data/loaders.py lm_synth)
        # through the same parameter every classifier uses
        kw.setdefault("vocab_size", num_classes)
        return GPTLM(**kw)
    if name == "mla_moe":
        from distributed_tensorflow_tpu.models.mla_moe import LatentMoELM

        if "param_dtype" in kw:
            kw["param_dtype"] = resolve_dtype(kw["param_dtype"])
        kw.setdefault("vocab_size", num_classes)
        return LatentMoELM(**kw)
    if name == "hybrid_ssm":
        from distributed_tensorflow_tpu.models.hybrid_ssm import HybridSSMLM

        if "param_dtype" in kw:
            kw["param_dtype"] = resolve_dtype(kw["param_dtype"])
        kw.setdefault("vocab_size", num_classes)
        return HybridSSMLM(**kw)
    if name == "window_moe":
        from distributed_tensorflow_tpu.models.window_moe import WindowMoELM

        if "param_dtype" in kw:
            kw["param_dtype"] = resolve_dtype(kw["param_dtype"])
        kw.setdefault("vocab_size", num_classes)
        return WindowMoELM(**kw)
    if name == "jamba":
        from distributed_tensorflow_tpu.models.jamba import JambaLM

        if "param_dtype" in kw:
            kw["param_dtype"] = resolve_dtype(kw["param_dtype"])
        kw.setdefault("vocab_size", num_classes)
        return JambaLM(**kw)
    if name not in _REGISTRY:
        raise KeyError(f"unknown model '{name}'; known: {sorted(_REGISTRY)} "
                       f"+ resnet20, bert_tiny, moe, gpt, mla_moe, "
                       f"hybrid_ssm, window_moe, jamba")
    return _REGISTRY[name](num_classes=num_classes, **kw)


def get_model_fn(name: str, num_classes: int = 10, **kw) -> Callable[[], nn.Module]:
    """Reference-style zero-arg model_fn (reference initializer.py:12)."""
    return lambda: create_model(name, num_classes=num_classes, **kw)
