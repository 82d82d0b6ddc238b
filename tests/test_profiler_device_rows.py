"""The device part of `fluid.profiler`'s report (PR 38) on hand-made
lists: a name stack's phase, op type and site, the rows by (phase, op
type, name scope) with Mosaic kernels and XLA's families as sub-rows, and
the roll-ups the benchmark's entries read.
"""
import pytest

from paddle_tpu import profiler


# ------------------------------------------------ the scopes' arithmetic
@pytest.mark.parametrize("op_name,want", [
    ("jit(stepped)/jvp(mul)/lm_head/dot_general",
     ("forward", "mul", "lm_head")),
    ("jit(stepped)/transpose(jvp(mul))/blk0/attn.q/transpose",
     ("backward", "mul", "blk0/attn.q")),
    ("jit(stepped)/jvp(mul)/dot_general", ("forward", "mul", "")),
    # a nested transform's or a kernel's name is no site
    ("jit(stepped)/transpose(jvp(kda_attention))/l1_kda/jvp(kda_attention)"
     "/while/body/mul", ("backward", "kda_attention", "l1_kda")),
    ("jit(stepped)/jit(main)/transpose(jvp(layer_norm))/layer_norm_bwd/"
     "pallas_call", ("backward", "layer_norm", "")),
    ("jit(stepped)/adam/mul", ("optimizer", "adam", "")),
    ("jit(stepped)/backward_macro/convert_element_type",
     ("optimizer", "backward_macro", "")),
    ("jit(stepped)/rng_key/jit(_threefry_fold_in)/mul",
     ("optimizer", "rng_key", "")),
    ("jit(stepped)/convert_element_type", None),
    ("", None), (None, None),
])
def test_a_name_stack_gives_phase_op_type_and_site(op_name, want):
    sites = {"lm_head", "blk0/attn.q", "blk0", "l1_kda"}
    assert profiler.classify(op_name, sites) == want


HLO = '''HloModule jit_stepped, entry_computation_layout={()->f32[]}

ENTRY %main.9 (p: f32[4]) -> f32[4] {
  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(stepped)/jvp(mul)/lm_head/dot_general"}
  %fusion.2 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(stepped)/transpose(jvp(mul))/lm_head/transpose"}
  %fusion.3 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(stepped)/jvp(mul)/l0_q/dot_general"}
  %layer_norm_fwd.1 = f32[4]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(stepped)/jvp(layer_norm)/ln0/layer_norm_fwd/pallas_call"}
  %copy.7 = f32[4]{0} copy(%p)
  ROOT %fusion.4 = f32[4]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(stepped)/adam/mul"}
}'''
SITES = {"lm_head", "l0_q", "ln0"}
DEVICE_OPS = [("fusion.1", 0.000, 0.004), ("fusion.3", 0.004, 0.001),
              ("tpu_custom_call/layer_norm_fwd.1", 0.005, 0.001),
              ("fusion.2", 0.006, 0.006), ("copy.7", 0.012, 0.001),
              ("while.3", 0.013, 0.001), ("fusion.4", 0.014, 0.001),
              ("fusion.1", 0.020, 0.001)]


def test_device_rows_by_phase_op_and_site_with_kernels_and_families():
    rows = profiler.device_rows([DEVICE_OPS], profiler.scopes_of(HLO),
                                SITES, steps=2)
    main = [(r["phase"], r["op"], r["scope"]) for r in rows
            if not r["kernel"]]
    assert main == [("backward", "mul", "lm_head"),
                    ("forward", "mul", "lm_head"),
                    ("-", "unscoped", ""), ("forward", "mul", "l0_q"),
                    ("forward", "layer_norm", "ln0"),
                    ("optimizer", "adam", "")]
    head = rows[1]
    assert head["calls"] == 2 and head["total_ms"] == pytest.approx(5.0)
    assert head["ms_per_step"] == pytest.approx(2.5)
    assert (head["min_ms"], head["max_ms"], head["mean_ms"]) \
        == pytest.approx((1.0, 4.0, 2.5))
    assert head["share"] == pytest.approx(5.0 / 16.0)
    # a Mosaic kernel under its op by its name; XLA's own by family
    subs = [(r["op"], r["kernel"], r["total_ms"]) for r in rows
            if r["kernel"]]
    assert subs == [("unscoped", "copy", pytest.approx(1.0)),
                    ("unscoped", "while", pytest.approx(1.0)),
                    ("layer_norm", "layer_norm_fwd", pytest.approx(1.0))]
    # the roll-ups are what the benchmark's entries read, ms a step
    assert profiler.rollup(rows, "op") == pytest.approx(
        {"mul": 6.0, "unscoped": 1.0, "layer_norm": 0.5, "adam": 0.5})
    assert profiler.rollup(rows, "phase") == pytest.approx(
        {"forward": 3.5, "backward": 3.0, "-": 1.0, "optimizer": 0.5})
    assert sum(profiler.rollup(rows, "op").values()) \
        == pytest.approx(16.0 / 2)


def test_device_rows_average_the_chips_and_need_no_text():
    rows = profiler.device_rows([DEVICE_OPS, DEVICE_OPS],
                                profiler.scopes_of(HLO), SITES, steps=2)
    assert rows[1]["calls"] == 2 and rows[1]["total_ms"] == pytest.approx(5.0)
    # no text: every op under its family, nothing claims a scope
    rows = profiler.device_rows([DEVICE_OPS], {}, (), steps=2)
    assert [r["op"] for r in rows if not r["kernel"]] == ["unscoped"]
    assert {r["kernel"]: r["total_ms"] for r in rows if r["kernel"]} \
        == pytest.approx({"fusion": 13.0, "layer_norm_fwd": 1.0,
                          "copy": 1.0, "while": 1.0})

