"""Run by hand, not part of tier-1 (like test_benchmark.py beside it):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

`scopes.py` on a hand-written HLO text and hand-made events, against sums
computed by hand; the six scope readers on what they cannot read.
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH]

import run      # noqa: E402
import scopes   # noqa: E402

READERS = ("device_step_ms.train", "backbone_dense_share.train",
           "head_loss_share.train", "opt_update_share.train",
           "unscoped_share.train", "opt_fused_share.train")

# One module as the compiler prints it: computations at column 0, their
# instructions indented. A fusion's own op_name is its root's.
HLO = '''HloModule jit_step, is_scheduled=true

%fused_computation.1 (param_0.1: bf16[8,8], param_1.1: f32[8,8]) -> bf16[8,8] {
  %param_0.1 = bf16[8,8]{1,0} parameter(0)
  %param_1.1 = f32[8,8]{1,0} parameter(1)
  %convert.1 = bf16[8,8]{1,0} convert(%param_1.1), metadata={op_name="jit(step)/TransformerBlock_0/fc1/amp_cast/convert_element_type"}
  ROOT %dot.1 = bf16[8,8]{1,0:T(8,128)(2,1)} dot(%param_0.1, %convert.1), metadata={op_name="jit(step)/TransformerBlock_0/fc1/jvp()/dot_general"}
}

%fused_computation.2 (param_0.2: f32[8,8], param_1.2: f32[8,8]) -> f32[8,8] {
  %param_0.2 = f32[8,8]{1,0} parameter(0)
  %param_1.2 = f32[8,8]{1,0} parameter(1)
  %multiply.7 = f32[8,8]{1,0} multiply(%param_0.2, %param_1.2), metadata={op_name="jit(step)/bwd/head/transpose(jvp())/mul"}
  ROOT %subtract.3 = f32[8,8]{1,0} subtract(%param_0.2, %multiply.7), metadata={op_name="jit(step)/opt/sub"}
}

%region_0.5 (reduce_sum.1: f32[], reduce_sum.2: f32[]) -> f32[] {
  %reduce_sum.1 = f32[] parameter(0), metadata={op_name="reduce_sum"}
  %reduce_sum.2 = f32[] parameter(1), metadata={op_name="reduce_sum"}
  ROOT %add.9 = f32[] add(%reduce_sum.1, %reduce_sum.2), metadata={op_name="jit(step)/sce/reduce_sum"}
}

ENTRY %main.9 (state_arrs_0_.1: f32[8,8], input_arrs_0_.1: bf16[8,8]) -> (f32[8,8], f32[]) {
  %state_arrs_0_.1 = f32[8,8]{1,0} parameter(0), metadata={op_name="state_arrs[0]"}
  %input_arrs_0_.1 = bf16[8,8]{1,0} parameter(1), metadata={op_name="input_arrs[0]"}
  %fusion.1 = bf16[8,8]{1,0:T(8,128)(2,1)} fusion(%input_arrs_0_.1, %state_arrs_0_.1), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step)/TransformerBlock_0/fc1/jvp()/dot_general"}
  %singa_flash_fwd.1 = (bf16[8,8]{1,0}, f32[8,8]{1,0}) custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/TransformerBlock_0/attn/jvp()/singa_flash_fwd/pallas_call"}
  %singa_flash_bwd.1 = bf16[8,8]{1,0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/bwd/TransformerBlock_0/attn/transpose(TransformerBlock_0)/attn/jvp()/singa_flash_bwd/pallas_call"}
  %copy.4 = f32[8,8]{0,1} copy(%state_arrs_0_.1)
  %reduce.2 = f32[] reduce(%copy.4, %state_arrs_0_.1), dimensions={0,1}, to_apply=%region_0.5, metadata={op_name="jit(step)/sce/reduce_sum"}
  %fusion.2 = f32[8,8]{1,0} fusion(%state_arrs_0_.1, %copy.4), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(step)/opt/sub"}
  %convert.8 = bf16[8,8]{1,0} convert(%fusion.2), metadata={op_name="jit(step)/head/amp_cast/convert_element_type"}
  %add.3 = f32[] add(%reduce.2, %reduce.2), metadata={op_name="jit(step)/add"}
  ROOT %tuple.1 = (f32[8,8]{1,0}, f32[]) tuple(%fusion.2, %add.3)
}
'''


@pytest.fixture
def hlo_dir(tmp_path):
    (tmp_path / "step_0123456789abcdef.hlo.txt").write_text(HLO)
    # another executable of the run, with a name the step has too
    (tmp_path / "serving_engine_step_0123456789abcdef.hlo.txt").write_text(
        "HloModule jit_decode_fn\n\nENTRY %main.1 () -> f32[] {\n"
        '  ROOT %fusion.1 = f32[] fusion(), kind=kLoop, calls=%f, '
        'metadata={op_name="jit(decode_fn)/decode/add"}\n}\n')
    return str(tmp_path)


# two programs in the stretch; `rogue.1` is in no text
TRACE = {"busy_s": 2.0, "window_s": 2.5,
         "self_s": {"fusion.1": 0.5, "singa_flash_fwd.1": 0.2,
                    "singa_flash_bwd.1": 0.3, "copy.4": 0.1,
                    "reduce.2": 0.25, "fusion.2": 0.4, "convert.8": 0.05,
                    "add.3": 0.15, "rogue.1": 0.05},
         "calls": {"fusion.1": 2, "singa_flash_fwd.1": 2,
                   "singa_flash_bwd.1": 2, "copy.4": 2, "reduce.2": 2,
                   "fusion.2": 2, "convert.8": 2, "add.3": 3, "rogue.1": 1}}


def test_scope_map_of_a_hand_written_text(hlo_dir):
    m = scopes.scope_map(hlo_dir)
    assert m["fusion.1"] == ("fwd", ("TransformerBlock_0", "fc1"))
    assert m["singa_flash_fwd.1"] == (
        "fwd", ("TransformerBlock_0", "attn", "singa_flash_fwd"))
    assert m["singa_flash_bwd.1"][0] == "bwd"
    assert m["singa_flash_bwd.1"][1][:2] == ("TransformerBlock_0", "attn")
    assert m["fusion.2"] == ("fwd", ("opt",))
    assert m["multiply.7"] == ("bwd", ("head",))
    assert m["convert.8"] == ("fwd", ("head", "amp_cast"))
    assert m["copy.4"] == ("fwd", None)       # the compiler made it
    assert m["add.3"] == ("fwd", ())          # named, no program scope
    assert "state_arrs_0_.1" not in m and "reduce_sum.1" not in m
    # the other executable's fusion.1 did not leak in
    assert scopes.scope_map(hlo_dir, "serving_engine_step") == {
        "fusion.1": ("fwd", ("decode",))}


def test_group_seconds_by_hand(hlo_dir):
    sec = lambda g: scopes.scope_seconds(TRACE, hlo_dir, g)
    assert sec("backbone") == (pytest.approx(1.0), 6)     # .5 + .2 + .3
    assert sec("head_loss") == (pytest.approx(0.25), 2)   # the reduce
    assert sec("opt") == (pytest.approx(0.4), 2)
    assert sec("other") == (pytest.approx(0.05), 2)       # head's amp cast
    assert sec("unscoped") == (pytest.approx(0.3), 6)     # .1 + .15 + .05
    assert scopes.unscoped_parts(TRACE, hlo_dir) == {
        "no_op_name": [pytest.approx(0.1), [(0.1, "copy.4")]],
        "no_scope": [pytest.approx(0.15), [(0.15, "add.3")]],
        "not_in_hlo": [pytest.approx(0.05), [(0.05, "rogue.1")]]}
    assert sum(sec(g)[0] for g in scopes.GROUPS) == pytest.approx(
        sum(TRACE["self_s"].values()))
    assert scopes.programs_run(TRACE, hlo_dir) == 2       # add.3 ran 3 times
    # fusion.2 holds head's backward and the optimizer and is counted at
    # its root; fusion.1 holds a matmul and the amp cast that feeds it
    assert scopes.mixed_fusion_seconds(TRACE, hlo_dir) == pytest.approx(0.9)
    assert scopes.mixed_fusion_seconds(
        TRACE, hlo_dir, among=("backbone", "head_loss", "opt")) \
        == pytest.approx(0.4)
    # fusion.2's root is the optimizer's, so no other group's fusion holds
    # `opt`; it holds head's backward, which is so counted under `opt`
    assert scopes.fused_elsewhere_seconds(TRACE, hlo_dir, "opt") == (0, 0)
    assert scopes.fused_elsewhere_seconds(TRACE, hlo_dir, "head_loss") \
        == (pytest.approx(0.4), 2)
    assert scopes.fused_elsewhere_seconds(TRACE, hlo_dir, "other") \
        == (pytest.approx(0.5), 2)       # fusion.1 holds the amp cast


def test_readers_by_hand(hlo_dir):
    rec = {"hlo_dir": hlo_dir}
    read = {n: run.load_module("layer_metrics", n).read(rec, TRACE)
            for n in READERS}
    assert read == {
        "device_step_ms.train": pytest.approx(1000.0),    # 2 s / 2 programs
        "backbone_dense_share.train": pytest.approx(25.0),   # 1.0 - .2 - .3
        "head_loss_share.train": pytest.approx(12.5),
        "opt_update_share.train": pytest.approx(20.0),
        "unscoped_share.train": pytest.approx(15.0),
        "opt_fused_share.train": pytest.approx(0.0)}
    share = run.load_module("layer_metrics", "attn_kernel_share.train")
    other = 100 * 0.05 / 2.0
    assert share.read(rec, TRACE) + other + sum(
        v for n, v in read.items() if n.endswith("share.train")
        and n != "opt_fused_share.train") == pytest.approx(100.0)


@pytest.mark.parametrize("name", READERS)
def test_readers_return_none_on_what_they_cannot_read(name, hlo_dir,
                                                      tmp_path):
    read = run.load_module("layer_metrics", name).read
    rec = {"hlo_dir": hlo_dir}
    assert read(rec, None) is None                     # no trace
    assert read(rec, {}) is None
    assert read(rec, dict(TRACE, busy_s=0.0, self_s={}, calls={})) is None
    assert read({}, TRACE) is None                     # no hlo_dir at all
    assert read({"hlo_dir": str(tmp_path / "missing")}, TRACE) is None
    bare = tmp_path / "bare"
    bare.mkdir()
    (bare / "step_0123456789abcdef.hlo.txt").write_text(
        "HloModule m\n\nENTRY %main.1 (a: f32[]) -> f32[] {\n"
        "  %a = f32[] parameter(0)\n  ROOT %add.1 = f32[] add(%a, %a)\n}\n")
    assert read({"hlo_dir": str(bare)}, TRACE) is None   # no op_name
    (bare / "step_fedcba9876543210.hlo.txt").write_text(HLO)
    assert read({"hlo_dir": str(bare)}, TRACE) is None   # two `step` texts


def test_a_fault_in_a_reader_raises(hlo_dir):
    """`None` is for nothing to read; a trace without its tables is a bug."""
    read = run.load_module("layer_metrics", "opt_update_share.train").read
    with pytest.raises(KeyError):
        read({"hlo_dir": hlo_dir}, {"busy_s": 2.0})


def test_a_program_without_scopes_gives_no_shares(tmp_path):
    """The parent commit's step: names, no scopes. Its step time reads; a
    share would claim that the optimizer takes 0 %."""
    (tmp_path / "step_0123456789abcdef.hlo.txt").write_text(
        "HloModule m\n\nENTRY %main.1 (a: f32[]) -> f32[] {\n"
        "  %a = f32[] parameter(0)\n  ROOT %add.3 = f32[] add(%a, %a), "
        'metadata={op_name="jit(step)/add"}\n}\n')
    rec = {"hlo_dir": str(tmp_path)}
    for name in READERS[1:]:
        assert run.load_module("layer_metrics", name).read(rec, TRACE) is None
    assert run.load_module("layer_metrics", READERS[0]).read(rec, TRACE) \
        == pytest.approx(1e3 * 2.0 / 3)       # add.3 ran three times


def test_the_command_prints_the_table(hlo_dir):
    lines = scopes.table_lines(TRACE, hlo_dir, top=3)
    text = "\n".join(lines)
    assert "2 programs" in lines[0] and "1000.000 ms a program" in lines[0]
    row = lambda start: next(ln.split() for ln in lines
                             if ln.startswith(start))
    assert row("backbone")[1:] == ["500.000", "50.00", "6"]
    assert row("TransformerBlock_0/fc1 fwd")[2:] == ["250.000", "25.00", "2"]
    assert row("TransformerBlock_0/attn bwd")[2:] == ["150.000", "15.00", "2"]
    assert row("(")[:2] == ["(6", "more)"]        # 9 rows, 3 shown
    assert "unscoped: no_op_name" in text and "copy.4" in text \
        and "rogue.1" in text
    # the kernels by the names their calls give them
    assert row("singa_flash_fwd fwd")[2:] == ["100.000", "10.00", "2"]
    assert row("singa_flash_bwd bwd")[2:] == ["150.000", "15.00", "2"]


def test_the_trace_is_split_by_module_and_each_text_finds_its_own(hlo_dir):
    """Two executables ran, both with a `fusion.1`; a third module has no
    text. Each text reads the operations of its own module events only."""
    mods = [("jit_step(11)", 0.0, 1.0), ("jit_decode_fn(22)", 1.0, 1.5),
            ("jit_step(11)", 2.0, 3.0), ("jit_other(33)", 3.0, 3.2)]
    ops = [("fusion.1", 0.1, 0.4), ("fusion.2", 0.5, 0.9),
           ("fusion.1", 1.1, 1.4), ("fusion.1", 2.1, 2.4),
           ("copy.4", 2.5, 2.6), ("x.1", 3.05, 3.1), ("stray.1", 5.0, 5.1)]
    events = scopes.by_module(ops, mods)
    assert {m: [e[0] for e in evs] for m, evs in events.items()} == {
        "jit_step(11)": ["fusion.1", "fusion.2", "fusion.1", "copy.4"],
        "jit_decode_fn(22)": ["fusion.1"], "jit_other(33)": ["x.1"],
        None: ["stray.1"]}
    step, decode = scopes.texts(hlo_dir, "step") + scopes.texts(
        hlo_dir, "serving_engine_step")
    assert scopes.module_of(step, events) == "jit_step(11)"
    assert scopes.module_of(decode, events) == "jit_decode_fn(22)"
    # a module that ran something the text does not name is not its module
    events["jit_step(11)"].append(("rogue.1", 2.7, 2.8))
    assert scopes.module_of(step, events) is None
    assert [os.path.basename(p) for p in scopes.texts(hlo_dir)] == [
        "serving_engine_step_0123456789abcdef.hlo.txt",
        "step_0123456789abcdef.hlo.txt"]


def test_benchmark_json_still_passes_its_own_test():
    import test_benchmark
    test_benchmark.test_benchmark_json_names_and_files()
    b = run.load_json("BENCHMARK.json")
    mine = [m for m in b["per_layer"] if m["name"] in READERS]
    assert [m["name"] for m in mine] == list(READERS)     # appended, in order
    assert b["per_layer"][-6:] == mine
    assert all(m["source"] == "device_trace" and m["workloads"]
               == ["train_gpt2m"] and m["moves"] == "train_tokens_per_s"
               for m in mine)
