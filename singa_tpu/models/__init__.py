"""Model zoo — TPU-native counterparts of the reference's example models
(examples/cnn/model/{cnn,alexnet,resnet,xceptionnet}.py, examples/mlp).

The registry names each model's constructor: a module's
`create_model(**kwargs)`, or a language model's class. Every classifier is
a `model.Model` whose `train_one_batch(x, y, dist_option, spars)`
dispatches to the DistOpt strategy named by `dist_option` (the reference
repeats this dispatch in every model file; here it lives once in
`base.Classifier`).
"""

from .. import observe
from .base import Classifier  # noqa: F401
from . import (mlp, cnn, alexnet, resnet, xceptionnet, transformer,  # noqa: F401
               looplm, mellum, sdar, lfm2, olmo_hybrid)

_REGISTRY = {
    "mlp": mlp.create_model,
    "cnn": cnn.create_model,
    "alexnet": alexnet.create_model,
    "resnet": resnet.resnet50,
    "resnet18": resnet.resnet18,
    "resnet34": resnet.resnet34,
    "resnet50": resnet.resnet50,
    "resnet101": resnet.resnet101,
    "resnet152": resnet.resnet152,
    "xceptionnet": xceptionnet.create_model,
    "gpt": transformer.create_model,
    "gpt_pipe": transformer.create_pipelined,
    "looplm": looplm.LoopLM,
    "mellum": mellum.Mellum,
    "sdar": sdar.SDAR,
    "lfm2": lfm2.LFM2,
    "olmo_hybrid": olmo_hybrid.OlmoHybrid,
}


def create_model(name: str, **kwargs):
    """Build a zoo model by name (the string taken by examples' --model)."""
    try:
        fn = _REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown model {name!r}; have {sorted(_REGISTRY)}")
    with observe.span("model.create"):
        return fn(**kwargs)
