"""Model registry — the TPU-native analogue of the reference's
``load_train_objs`` model seam (multigpu.py:122-126), which makes the Trainer
model-agnostic."""
from __future__ import annotations

import importlib
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax


class ModelDef(NamedTuple):
    name: str
    init: Callable[..., Tuple[Dict, Dict]]
    apply: Callable[..., Tuple[jax.Array, Dict]]
    # ``(vocabulary, sequence length)`` of a model whose input is token
    # ids ``i32[B,T]`` and whose loss is a position's; None for a model
    # that takes images.  What the Trainer and the CLI branch on: a
    # property of the model, not a list of names.
    tokens: Optional[Tuple[int, int]] = None


# name -> (module, init, apply, tokens): models that are modules of
# constants; ``tokens`` names the module's (vocabulary, length) constants.
_MODULE_MODELS = {
    "vgg": ("vgg", "init", "apply", None),
    "deepnn": ("deepnn", "init", "apply", None),
    "resnet18": ("resnet", "init", "apply", None),
    "transformer": ("transformer", "init", "apply", None),
    "tinylm": ("transformer", "lm_init", "lm_apply", ("VOCAB", "T_MAX")),
}
# name -> module with ``build(config) -> (init, apply, tokens)``: models
# built from a configuration file (``--model_config``).
_CONFIG_MODELS = {"nemotron_h": "nemotron_h", "sambay": "sambay",
                  "glm4_moe_lite": "glm4_moe_lite", "laguna": "laguna",
                  "granite_hybrid": "granite_hybrid"}
MODEL_NAMES = tuple(_MODULE_MODELS) + tuple(_CONFIG_MODELS)


def get_model(name: str, config: Optional[dict] = None) -> ModelDef:
    """The model of that name.  ``config`` is read by the models that are
    built from a configuration and ignored by the others."""
    if name in _MODULE_MODELS:
        module, init, apply, tokens = _MODULE_MODELS[name]
        mod = importlib.import_module(f"{__name__}.{module}")
        return ModelDef(name, getattr(mod, init), getattr(mod, apply),
                        tokens and tuple(getattr(mod, c) for c in tokens))
    if name in _CONFIG_MODELS:
        if config is None:
            raise ValueError(
                f"model {name!r} is built from a configuration file: pass "
                "--model_config <file> (get_model(name, config))")
        mod = importlib.import_module(f"{__name__}.{_CONFIG_MODELS[name]}")
        return ModelDef(name, *mod.build(config))
    raise ValueError(f"unknown model {name!r}; available: "
                     + ", ".join(MODEL_NAMES))
