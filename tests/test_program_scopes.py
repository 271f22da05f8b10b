"""Program scopes on the device work (ISSUE 25): every instruction of a traced
step carries, in its `op_name`, the path of the layers it was recorded under
(the attribute path `get_params()` keys use), the tape's backward runs under
the same path behind a leading `bwd`, the optimizer's update sits under
`opt`, the `amp` casts under `amp_cast`, the engine's programs under
`prefill.b<bucket>` / `decode`, and the Pallas kernels have names.

Read from the compiled text `introspect.capture_hlo` writes, with the
benchmark's own parser (`benchmark/scopes.py`, loaded by path) — what the
`--trace 1` run of the benchmark reads on the chip.
"""

import glob
import importlib.util
import os

import jax
import numpy as np
import pytest

from singa_tpu import device, introspect, models, opt, tensor

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_scopes():
    spec = importlib.util.spec_from_file_location(
        "benchmark_scopes", os.path.join(ROOT, "benchmark", "scopes.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


scopes = _load_scopes()


def _tiny_gpt(dev, layers=2):
    return models.create_model("gpt", vocab_size=211, max_seq=64, dim=32,
                               num_heads=2, num_layers=layers, mlp_ratio=4,
                               attn_bias=True)


@pytest.fixture(scope="module")
def step(tmp_path_factory):
    """A tiny GPT graph step with `amp` and Adam, run twice; (model, the
    parsed instruction table of its `step` executable, its two inputs)."""
    out = tmp_path_factory.mktemp("hlo")
    dev = device.get_default_device()
    m = _tiny_gpt(dev)
    m.set_optimizer(opt.Adam(lr=1e-3))
    ids = np.random.RandomState(0).randint(0, 211, (2, 32)).astype(np.int32)
    tx, ty = (tensor.from_numpy(ids, device=dev) for _ in range(2))
    m.compile([tx], is_train=True, use_graph=True, amp="bfloat16")
    introspect.capture_hlo(str(out))
    try:
        losses = [float(m(tx, ty)[1].numpy()) for _ in range(2)]
    finally:
        introspect.capture_hlo(None)
    assert losses[1] < losses[0]
    return m, scopes.instructions(str(out), "step"), (tx, ty)


def _paths(table, phase=None):
    return [i["path"] for i in table.values()
            if i["path"] and phase in (None, i["phase"])]


def test_nearly_every_named_instruction_carries_a_program_scope(step):
    _m, table, _ = step
    named = [i for i in table.values() if i["path"] is not None]
    scoped = [i for i in named if i["path"]]
    assert len(named) > 1000
    assert len(scoped) >= 0.97 * len(named), \
        sorted({i["opcode"] for i in named if not i["path"]})
    # the step's parts outside the layers have their fixed scopes
    firsts = {p[0] for p in _paths(table)}
    assert {"opt", "tok_embed", "pos_embed", "head", "sce", "ln_f",
            "TransformerBlock_0", "TransformerBlock_1"} <= firsts
    assert any("amp_cast" in p for p in _paths(table))


def test_opt_scope_holds_the_update_and_none_of_the_backward_pass(step):
    _m, table, _ = step
    under_opt = [i for i in table.values()
                 if i["path"] and "opt" in i["path"]]
    assert len(under_opt) > 100          # Adam on 37 parameters
    assert {i["phase"] for i in under_opt} == {"fwd"}
    assert {i["path"] for i in under_opt} == {("opt",)}
    # and the blocks' backward is there, under the blocks
    bwd_firsts = {p[0] for p in _paths(table, "bwd")}
    assert {"TransformerBlock_0", "TransformerBlock_1", "head", "ln_f",
            "tok_embed"} <= bwd_firsts
    assert scopes.group_of(("opt",)) == "opt"


def test_hand_written_backward_runs_under_its_forward_scope(step):
    _m, table, _ = step
    from singa_tpu import autograd
    # both operators carry a rule of their own, not the vjp-derived one
    for op in (autograd.SoftMaxCrossEntropy, autograd.ComputeCast):
        assert op.backward is not autograd.Operator.backward
    for phase in ("fwd", "bwd"):
        paths = _paths(table, phase)
        assert ("sce",) in paths, phase
        assert ("head", "amp_cast") in paths, phase
    # a vjp-derived rule too: `bwd/<scope>/transpose(jvp())/...`
    assert ("TransformerBlock_1", "fc1") in _paths(table, "bwd")
    # the scope remembered is jax's own name stack, whoever opened it: the
    # model's `pos_embed` around operators that sit in no layer
    assert ("pos_embed",) in _paths(table, "bwd")


def test_the_loss_takes_the_logits_as_the_head_wrote_them(step):
    """The integer-target cross-entropy of the GPT step asks for no other
    view of the (B, S, V) logits and gathers nothing from an array of their
    size: on the chip a 2-D view is a relayout of them, and the gather read
    a log-probability tensor written out for it (ISSUE 31). From the
    lowered text; what the chip's compiler then forms of the logits' size
    is `test_tpu_compile.py`'s to say."""
    import re
    m, _table, _ = step
    text = m.lower_step().as_text()
    logits = re.compile(r"tensor<(2x32x211|64x211)xf32>")
    assert logits.search(text)              # the head's output is there
    for line in text.splitlines():
        if logits.search(line):
            assert not re.search(r"gather|dynamic_slice|reshape", line), line


def test_operator_outside_any_scope_reads_as_its_own_name():
    import jax
    from singa_tpu import autograd

    def f(a):
        x = tensor.Tensor(data=a, requires_grad=True, stores_grad=True)
        y = autograd.reshape(x, (-1,))              # no layer, no scope
        with jax.named_scope("mine"):
            z = autograd.reshape(y, (2, -1))        # a scope: no leaf added
        return z.data, [g.data for _p, g in
                        autograd.backward(autograd.sum(z))]

    was, autograd.training = autograd.training, True
    try:
        text = jax.jit(f).lower(np.ones((4, 2), np.float32)).as_text(
            debug_info=True)
    finally:
        autograd.training = was
    import re
    names = set(re.findall(r'"(jit\(f\)/[^"]*)"', text))
    assert any(n.startswith("jit(f)/Reshape/") for n in names), names
    assert any(n.startswith("jit(f)/mine/") for n in names), names
    assert not any("mine/Reshape" in n for n in names), names
    assert any(n.startswith("jit(f)/bwd/mine/") for n in names), names
    assert any(n.startswith("jit(f)/bwd/Reshape/") for n in names), names


def test_top_level_layers_read_as_their_attribute_not_their_class(step):
    m, table, _ = step
    firsts = {p[0] for p in _paths(table)}
    assert not firsts & {"Linear", "Embedding", "LayerNorm",
                         "SoftMaxCrossEntropy", "MultiHeadAttention"}
    # an instruction's scope is the prefix of the parameter it reads
    keys = set(m.get_params())
    depth2 = {"/".join(p[:2]) for p in _paths(table) if len(p) > 1
              and p[0].startswith("TransformerBlock_")}
    for sc in ("TransformerBlock_0/attn", "TransformerBlock_1/fc2",
               "TransformerBlock_0/ln1"):
        assert sc in depth2
        assert any(k.startswith(sc.replace("/", ".") + ".") for k in keys)
    for top in ("head", "tok_embed", "ln_f"):
        assert any(k.startswith(top + ".") for k in keys)


def test_groups_cover_the_step(step):
    _m, table, _ = step
    count = {}
    for i in table.values():
        if i["path"]:
            g = scopes.group_of(i["path"])
            count[g] = count.get(g, 0) + 1
    assert set(count) == {"backbone", "head_loss", "opt", "other"}
    # `other` is the amp casts here and nothing else
    assert all("amp_cast" in i["path"] for i in table.values()
               if i["path"] and scopes.group_of(i["path"]) == "other")


def test_pallas_calls_of_the_traced_step_carry_the_kernels_names(step):
    m, _table, (tx, ty) = step
    fn = m._step_builder(0).fn      # tag 0's jitted step, traced afresh
    # tracing parks tracers in the model: the guard puts the arrays back
    with m._tracers_kept_out(m._state_tensors) as (state, opt_arrs, rng):
        jaxpr = fn.trace(state, opt_arrs, rng, [tx.data, ty.data]).jaxpr
    assert not any(isinstance(t.data, jax.core.Tracer)
                   for t in m._state_tensors)

    names = []

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)
    walk(jaxpr.jaxpr)
    # 2 blocks: a forward kernel each, and the backward (fused, or dq + dkv)
    assert names.count("singa_flash_fwd") == 2, names
    bwd = [n for n in names if n.startswith("singa_flash_bwd")]
    assert len(bwd) >= 2 and set(names) <= {
        "singa_flash_fwd", "singa_flash_bwd", "singa_flash_bwd_dq",
        "singa_flash_bwd_dkv"}, names


def test_engine_programs_carry_prefill_bucket_and_decode_scopes(tmp_path):
    from singa_tpu import engine as eng
    dev = device.get_default_device()
    m = _tiny_gpt(dev)
    ids = tensor.from_numpy(np.zeros((2, 8), np.int32), device=dev)
    m.compile([ids], is_train=False, use_graph=False)
    m.eval()
    introspect.capture_hlo(str(tmp_path))
    e = eng.ServingEngine(m, max_slots=2, page_size=8, max_ctx=64,
                          prompt_buckets=(16,), steps_per_sync=2).start()
    try:
        r = e.submit(np.arange(9, dtype=np.int32), 4)
        assert r.wait(300) and r.outcome == "completed"
    finally:
        e.stop()
        introspect.capture_hlo(None)

    def read(key):
        files = glob.glob(os.path.join(str(tmp_path), key + "_*.hlo.txt"))
        assert files, os.listdir(str(tmp_path))
        instrs = []
        for f in files:
            with open(f, encoding="utf-8") as fh:
                instrs += [i for i in scopes.parse_hlo(fh.read()).values()
                           if i["path"]]
        return instrs

    def holds(instrs, *comps):
        """Some path holds `comps` side by side."""
        n = len(comps)
        return any(p[k:k + n] == comps for p in {i["path"] for i in instrs}
                   for k in range(len(p) - n + 1))

    pre = read("serving_engine_prefill")
    assert {i["path"][0] for i in pre} == {"prefill.b16"}
    for comps in (("TransformerBlock_0", "attn"), ("TransformerBlock_1", "fc2"),
                  ("TransformerBlock_0", "ln1"), ("prefill.b16", "tok_embed"),
                  ("prefill.b16", "ln_f"), ("prefill.b16", "head")):
        assert holds(pre, *comps), comps
    dec = read("serving_engine_step")
    # everything the program runs reads `decode`; the few that do not are
    # the scalar reducers (add, maximum) inside the scan's `reduce`s, which
    # jax names from the scan body's own stack
    assert {i["path"][0] for i in dec if i["entry"]} == {"decode"}
    stray = [i for i in dec if i["path"][0] != "decode"]
    assert len(stray) <= 0.03 * len(dec)
    assert {i["opcode"] for i in stray} <= {"add", "maximum"}
    for comps in (("TransformerBlock_0", "attn"), ("TransformerBlock_1", "fc1"),
                  ("TransformerBlock_1", "ln2"), ("tok_embed",), ("ln_f",),
                  ("head",)):
        assert holds(dec, *comps), comps


def test_scope_names_follow_registration():
    from singa_tpu import layer

    class Net(layer.Layer):
        def __init__(self):
            super().__init__()
            self.first = layer.Linear(4)
            self.register_layers(layer.ReLU(), layer.Linear(3))

    n = Net()
    assert n.first._scope == "first" and n.first.name == "Linear"
    assert [s._scope for s in n.sublayers().values()] == \
        ["first", "ReLU_0", "Linear_1"]
    assert layer.Linear(2, name="mine")._scope is None   # falls back to name


@pytest.mark.parametrize("op_name,want", [
    ("jit(step)/TransformerBlock_3/attn/jvp()/dot_general",
     ("fwd", ("TransformerBlock_3", "attn"))),
    ("jit(step)/bwd/TransformerBlock_3/fc1/transpose(jvp())/dot_general",
     ("bwd", ("TransformerBlock_3", "fc1"))),
    ("jit(step)/transpose(jvp(TransformerBlock_0/attn))/mul",
     ("bwd", ("TransformerBlock_0", "attn"))),
    ("jit(step)/tok_embed/jvp(jit(_take))/jit(_where)/select_n",
     ("fwd", ("tok_embed",))),
    ("jit(step)/bwd/sce/jit(_one_hot)/eq", ("bwd", ("sce",))),
    ("jit(step)/opt/mul", ("fwd", ("opt",))),
    ("jit(step)/mul", ("fwd", ())),
    ("reduce_sum", ("fwd", ())),
    ("jit(step)/jvp()/broadcast_in_dim;jit(step)/jvp()/mul", ("fwd", ())),
    ("jit(step)/head/jvp()/dot_general;jit(step)/bwd/sce/sub",
     ("fwd", ("head",))),
    # a `lax.scan` body: jax's control-flow wrappers are no scopes
    ("jit(decode_fn)/decode/while/body/closed_call/TransformerBlock_2/attn/"
     "dot_general", ("fwd", ("decode", "TransformerBlock_2", "attn"))),
    ("jit(decode_fn)/decode/while/body/closed_call", ("fwd", ("decode",))),
    ("pools[24][1]", ("fwd", ())),      # a copy named after its argument
])
def test_parse_op_name(op_name, want):
    assert scopes.parse_op_name(op_name) == want


@pytest.mark.parametrize("path,want", [
    (("decode", "TransformerBlock_2", "attn"), "backbone"),
    (("prefill.b128", "head"), "head_loss"),
    (("prefill.b128",), "other"),       # the program's own scope, no layer
    (("decode", "tok_embed"), "backbone"),
    (("pos_embed",), "backbone"), (("sce",), "head_loss"),
    (("TransformerBlock_0", "fc1", "amp_cast"), "other"),
    (("Reshape",), "other"), ((), "unscoped"), (None, "unscoped"),
])
def test_group_of(path, want):
    assert scopes.group_of(path) == want
