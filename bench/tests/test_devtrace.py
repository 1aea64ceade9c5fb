"""The trace reduction on synthetic traces: nesting and self time, busy and
idle time, layer attribution, and the join of op names to the compiled
module's metadata (the format of XLA's HLO text, stack frame tables
included)."""

import pytest

import devtrace
import run
from devtrace import Op

HLO = """HloModule jit__pic_run_window_impl

%fused_computation.1 (p: f32[8]) -> f32[8] {
  ROOT %x = f32[8]{0} add(%p, %p)
}

ENTRY %main {
  %fusion.7 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_pic_run_window_impl)/while/body/closed_call/jit(gpma_update)/add" stack_frame_id=3}
  %sort.2 = s32[8]{0} sort(%b), dimensions={0}, metadata={op_name="jit(_pic_run_window_impl)/while/body/closed_call/cond/branch_1_fun/jit(argsort)/sort" stack_frame_id=4}
  %copy.1 = f32[8]{0} copy(%c)
  ROOT %convolution.3 = f32[8]{0} convolution(%d, %e), metadata={op_name="jit(_pic_run_window_impl)/while/body/closed_call/dot_general" stack_frame_id=5}
}

FileNames
1 "/x/src/repro/pic/simulation.py"
2 "/x/src/repro/core/binning.py"
3 "/x/src/repro/core/deposition.py"

FunctionNames
1 "_pic_step"
2 "sort_permutation"
3 "global_sort_device"
4 "deposit_current_matrix_fused"

FileLocations
1 {file_name_id=1 function_name_id=1 line=10 end_line=10 column=0 end_column=1}
2 {file_name_id=2 function_name_id=2 line=20 end_line=20 column=0 end_column=1}
3 {file_name_id=1 function_name_id=3 line=30 end_line=30 column=0 end_column=1}
4 {file_name_id=3 function_name_id=4 line=40 end_line=40 column=0 end_column=1}

StackFrames
1 {file_location_id=1 parent_frame_id=1}
2 {file_location_id=3 parent_frame_id=1}
3 {file_location_id=1 parent_frame_id=1}
4 {file_location_id=2 parent_frame_id=2}
5 {file_location_id=4 parent_frame_id=2}
"""


def op(start, end, name, text=None):
    return Op(float(start), float(end), name, text if text is not None else name)


def test_instruction_name():
    assert devtrace.instruction_name("%fusion.2421 = s32[2097152]{0} fusion(%a), kind=kCustom") == "fusion.2421"
    assert devtrace.instruction_name("%while.158 = (s32[]) while(%t)") == "while.158"


def test_nesting_gives_self_time():
    ops = devtrace.nest([op(0, 100, "while.1"), op(10, 30, "a"), op(40, 90, "while.2"), op(50, 60, "b")])
    by = {o.name: o for o in ops}
    assert by["while.1"].self_ns == 30 and by["while.2"].self_ns == 40
    assert by["a"].self_ns == 20 and by["b"].self_ns == 10


def test_busy_idle_union_and_window():
    # a fusion's own time between the ops nested in it is busy, not idle
    ops = [op(0, 100, "fusion.1"), op(10, 30, "a"), op(30, 40, "b"), op(120, 130, "c")]
    host = [(0.0, 200.0, "bench.window"), (135.0, 190.0, "bench.fetch_bundle"), (0.0, 200.0, "python")]
    red = devtrace.reduce_events({0: ops}, host, [])
    assert red.window_s == pytest.approx(200e-9)
    assert red.busy_s == pytest.approx(110e-9)
    # the longest gap, [130, 200], is covered innermost by the fetch span
    assert red.idle_gaps[0] == ["bench.fetch_bundle", pytest.approx(70e-9)]
    assert sorted(g[1] for g in red.idle_gaps) == pytest.approx([20e-9, 70e-9])


def test_ops_outside_the_window_do_not_count():
    ops = [op(0, 50, "a"), op(150, 250, "b")]
    red = devtrace.reduce_events({0: ops}, [(100.0, 200.0, "bench.window")], [])
    assert red.busy_s == pytest.approx(50e-9)


def test_busy_is_averaged_over_devices():
    host = [(0.0, 100.0, "bench.window")]
    red = devtrace.reduce_events({0: [op(0, 100, "a")], 1: [op(0, 50, "a")]}, host, [])
    assert red.busy_s == pytest.approx(75e-9)


def test_attribution_follows_the_layer_files():
    layers = devtrace.compile_layers(run.load_layers())
    meta = devtrace.hlo_metadata(HLO)
    assert devtrace.attribute("fusion.7 " + meta["fusion.7"], layers) == "sort"
    assert devtrace.attribute("sort.2 " + meta["sort.2"], layers) == "sort"
    assert devtrace.attribute("convolution.3 " + meta["convolution.3"], layers) == "deposition"
    assert devtrace.attribute("copy.1 ", layers) is None
    text = "fusion.9 jit(_pic_run_window_impl)/while/body/closed_call/jit(_gather_fields_fused_jit)/mul"
    assert devtrace.attribute(text, layers) == "gather"
    assert devtrace.attribute("fusion.10 jit(x)/jit(push_b)/sub", layers) == "maxwell"
    assert devtrace.attribute("fusion.11 jit(x)/jit(boris_push)/mul", layers) == "push"


def test_innermost_frame_decides():
    # unfold_guards lives in rhocell.py but is called by the gather
    layers = devtrace.compile_layers(run.load_layers())
    text = ("gather.1 jit(x)/jit(_take)/gather\n/x/src/repro/core/rhocell.py:unfold_guards\n"
            "/x/src/repro/pic/simulation.py:_gather_fields\n/x/src/repro/pic/simulation.py:_pic_step")
    assert devtrace.attribute(text, layers) == "gather"
    text = "add.1 jit(x)/add\n/x/src/repro/core/rhocell.py:fold_guards\n/x/src/repro/pic/simulation.py:_deposit_current"
    assert devtrace.attribute(text, layers) == "deposition"


def test_stack_frames_resolve_innermost_first():
    meta = devtrace.hlo_metadata(HLO)
    lines = meta["sort.2"].split("\n")
    assert lines[0].endswith("jit(argsort)/sort")
    assert lines[1:] == ["/x/src/repro/core/binning.py:sort_permutation",
                         "/x/src/repro/pic/simulation.py:_pic_step"]
    assert "copy.1" not in meta


def test_layer_time_and_unclaimed_share():
    layers = run.load_layers()
    ops = [op(0, 60, "fusion.1", "fusion.1 jit(w)/jit(gpma_update)/add"),
           op(60, 90, "fusion.2", "fusion.2 jit(w)/jit(_deposit_current_matrix_fused_jit)/dot"),
           op(90, 100, "copy.3", "copy.3 ")]
    red = devtrace.reduce_events({0: ops}, [(0.0, 100.0, "bench.window")], layers)
    assert red.layer_s == {"sort": pytest.approx(60e-9), "deposition": pytest.approx(30e-9)}
    assert red.unclaimed_share == pytest.approx(0.1)
    assert red.unclaimed_top[0][0].startswith("copy.3")
    assert red.top_ops[0][0].startswith("fusion.1 (sort")
    assert set(red.breakdown()) == {"device_ops", "idle_gaps"}


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        devtrace.reduce_events({0: [op(0, 1, "a")]}, [(0.0, 1.0, "python")], [])


def window_ops():
    """Two executables on one device: a small one, then the window whose
    instructions are those of `HLO`."""
    modules = [(0.0, 10.0, "jit_convert_element_type"), (20.0, 200.0, "jit__pic_run_window_impl")]
    ops = [
        Op(1.0, 5.0, "copy.1", "copy.1", event="%copy.1 = s32[3]{0:T(128)} copy(s32[3]{0:T(128)} %args_0_.1)"),
        Op(30.0, 90.0, "fusion.7", "fusion.7", event="%fusion.7 = f32[8]{0:T(256)} fusion(f32[8]{0} %a), kind=kLoop"),
        Op(90.0, 120.0, "convolution.3", "convolution.3",
           event="%convolution.3 = f32[8]{0} convolution(%d, %e)"),
        Op(120.0, 130.0, "copy.1", "copy.1", event="%copy.1 = f32[8]{0} copy(%c)"),
    ]
    devtrace.assign_modules(ops, modules)
    return ops


def test_signature_drops_layouts():
    assert devtrace.signature("%fusion.7 = f32[8]{0:T(256)} fusion(f32[8]{0} %a), kind=kLoop") == \
        ("fusion.7", "f32[8]", "fusion")
    sig = devtrace.signature("  ROOT %t = (s32[4]{0}, f32[2,2]{1,0:T(8,128)}) tuple(%a, %b)")
    assert sig == ("t", "(s32[4],f32[2,2])", "tuple")
    assert devtrace.signature("%fusion.7 = f32[8]{0:T(2") is None
    assert devtrace.module_name("jit__pic_run_window_impl(8178958833973486692)") == "jit__pic_run_window_impl"
    assert devtrace.hlo_module_name(HLO) == "jit__pic_run_window_impl"


def test_ops_belong_to_the_module_that_holds_them():
    ops = window_ops()
    assert [o.module for o in ops] == ["jit_convert_element_type"] + ["jit__pic_run_window_impl"] * 3
    assert devtrace.check_module(ops, "jit__pic_run_window_impl", HLO) == 3


def test_another_module_is_never_joined_by_name():
    # copy.1 is an instruction of the window module too; the small
    # module's copy.1 must not take the window's metadata or a layer
    ops = window_ops()
    devtrace.label_ops(ops, "jit__pic_run_window_impl", devtrace.hlo_metadata(HLO))
    assert ops[0].module == devtrace.FOREIGN and "[jit_convert_element_type]" in ops[0].text
    host = [(0.0, 200.0, "bench.window")]
    layers = [{"key": "copies", "patterns": ["^copy"]}] + run.load_layers()
    red = devtrace.reduce_events({0: ops}, host, layers)
    assert red.layer_s["copies"] == pytest.approx(10e-9)
    assert red.layer_s["sort"] == pytest.approx(60e-9)
    assert red.unclaimed_top[0][0].startswith("copy.1 [jit_convert_element_type] (unclaimed: other module)")


def test_a_trace_of_another_program_is_refused():
    ops = window_ops()
    ops[1].event = "%fusion.7 = s32[8]{0} fusion(%a), kind=kLoop"
    with pytest.raises(ValueError, match="do not match the recompiled window module"):
        devtrace.check_module(ops, "jit__pic_run_window_impl", HLO)
    ops = window_ops()
    ops[2].name = "convolution.9"
    with pytest.raises(ValueError, match="not in the recompiled module"):
        devtrace.check_module(ops, "jit__pic_run_window_impl", HLO)
    with pytest.raises(ValueError, match="no op of the module"):
        devtrace.check_module(window_ops(), "jit_other", HLO)
