"""The language models' skeleton (models/lm.py) and the block it repeats
(layer.TransformerBlock and its descriptions): the one recompute rule, the
mixers each built under their own name, the plan gauges' one helper, and
the model files' independence from one another.
"""

import ast
import os

import numpy as np
import pytest

from singa_tpu import autograd, device, layer, observe, tensor
from singa_tpu.models import lm

MODELS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "singa_tpu", "models")


def _blocks(n):
    return [layer.TransformerBlock(layer.Attention(2), layer.FeedForward(
        "swiglu", 8, bias=False), layer.Norms("rms")) for _ in range(n)]


@pytest.mark.parametrize("training", [True, False])
@pytest.mark.parametrize("recompute, want", [
    (False, 0), (0, 0), (True, 3), (2, 2), (5, 3)])
def test_recompute_rule(recompute, want, training, monkeypatch):
    """True: every block; n: the first n (no more than there are); and
    only while training. The trunk makes exactly those blocks regions."""
    m = lm.LM(16, 8, _blocks(3), 1e-6, recompute=recompute)
    monkeypatch.setattr(autograd, "training", training)
    assert m.rebuilt() == (want if training else 0)
    made = []
    region = autograd.region

    def spy(fn, *xs, reads=()):
        made.append(fn.__defaults__[0])     # the block's index
        return region(fn, *xs, reads=reads)

    monkeypatch.setattr(autograd, "region", spy)
    dev = device.get_default_device()
    x = tensor.from_numpy(np.ones((1, 4, 8), np.float32), device=dev)
    h, counts = m.trunk(x)
    assert h.shape == (1, 4, 8) and counts == [[], [], []]
    assert made == list(range(want if training else 0))


@pytest.mark.parametrize("mixer, attr", [
    (layer.Attention(2), "attn"), (layer.Convolution(3), "conv"),
    (layer.DeltaRule(2, 4, 4), "linear_attn")])
def test_each_mixer_builds_its_layer_under_its_own_name(mixer, attr):
    blk = layer.TransformerBlock(mixer)
    names = set(blk.sublayers())
    assert attr in names
    assert not names & ({"attn", "conv", "linear_attn"} - {attr})
    assert {"ln1", "ln2"} <= names and "moe" not in names
    with pytest.raises(AssertionError):
        layer.TransformerBlock(attr)


def test_plan_gauges_follow_the_instrumentation_switch():
    """`observe.record_plan` sets a gauge of `observe.PLANS` by kind, and,
    like every other record_* helper, nothing while the instrumentation is
    off; neither does `record_rows`, which reads the expert plan."""
    reg = observe.get_registry()
    observe.enable(False)
    try:
        observe.record_plan("singa_loop_plan", passes=4)
        lm.record_rows(np.ones((2, 4)))
        assert reg.get("singa_loop_plan") is None
        assert reg.get("singa_moe_rows") is None
    finally:
        observe.enable(True)
    observe.record_plan("singa_loop_plan", passes=4, blocks=2)
    g = reg.get("singa_loop_plan")
    assert (g.value(kind="passes"), g.value(kind="blocks")) == (4, 2)
    assert g.help == observe.PLANS["singa_loop_plan"]


def test_no_model_file_imports_a_private_name_of_another():
    """A model file takes what it shares from the skeleton's public names;
    no file under singa_tpu/models/ reaches into another's underscored
    ones, by `from .x import _y` or by `x._y` after `from . import x`."""
    siblings = {f[:-3] for f in os.listdir(MODELS) if f.endswith(".py")}
    found = []
    for f in sorted(siblings):
        tree = ast.parse(open(os.path.join(MODELS, f + ".py")).read())
        bound = {}
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom) and node.level == 1:
                for a in node.names:
                    if node.module in siblings and a.name.startswith("_"):
                        found.append((f, node.module, a.name))
                    elif node.module is None and a.name in siblings:
                        bound[a.asname or a.name] = a.name
        found += [(f, bound[n.value.id], n.attr) for n in ast.walk(tree)
                  if isinstance(n, ast.Attribute) and n.attr.startswith("_")
                  and isinstance(n.value, ast.Name) and n.value.id in bound]
    assert not found, found
