"""Fused QKV/KV projections in multi_head_attention (perf: one
[d, 3d]-column matmul on the MXU instead of three [d, d]).

Equivalence: with the fused weight set to the concatenation of the
three unfused weights, outputs and gradients must match the unfused
layout exactly. Ref: the reference's machine_translation builds the
three projections separately; fusion is a TPU layout choice, not a
semantic change.
"""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers

from paddle_tpu.ops.pallas import flash_attention as fa

B, T, D, H = 2, 6, 16, 4
DK = D // H


def _build(fused, seed=5, cross=False, T=T, D=D, H=H, train=True):
    main, startup = pt.Program(), pt.Program()
    main.random_seed = startup.random_seed = seed
    with pt.program_guard(main, startup):
        with pt.unique_name.guard():
            q_in = layers.data("q", shape=[T, D])
            kv_in = layers.data("kv", shape=[T, D]) if cross else q_in
            out = layers.multi_head_attention(
                q_in, kv_in, kv_in, d_key=D // H, d_value=D // H,
                d_model=D, n_head=H, name="attn", fused_qkv=fused)
            loss = layers.mean(out)
            if train:
                pt.optimizer.SGD(0.1).minimize(loss)
    return main, startup, loss


def _params(main, scope):
    return {p.name: np.asarray(scope.get(p.name))
            for p in main.all_parameters()}


def _losses_fused_and_unfused(cross, T=T, D=D, H=H):
    """Three SGD steps of the fused and the unfused program from the
    same weights (the fused ones the concatenation of the unfused)."""
    rng = np.random.RandomState(0)
    feed = {"q": rng.randn(B, T, D).astype("float32")}
    if cross:
        feed["kv"] = rng.randn(B, T, D).astype("float32")
    dims = dict(T=T, D=D, H=H)

    main_u, startup_u, loss_u = _build(False, cross=cross, **dims)
    scope_u = pt.Scope()
    exe = pt.Executor(pt.CPUPlace())
    with pt.scope_guard(scope_u):
        exe.run(startup_u)
        pu = _params(main_u, scope_u)

    main_f, startup_f, loss_f = _build(True, cross=cross, **dims)
    scope_f = pt.Scope()
    with pt.scope_guard(scope_f):
        exe.run(startup_f)
        pf = _params(main_f, scope_f)
        # overwrite fused weights with the concatenated unfused ones
        uw = {n.split(".")[0].rsplit("_", 1)[-1]: v
              for n, v in pu.items() if ".w" in n}
        for n in pf:
            if "_qkv.w" in n:
                scope_f.set(n, np.concatenate(
                    [uw["q"], uw["k"], uw["v"]], axis=1))
            elif "_kv.w" in n:
                scope_f.set(n, np.concatenate([uw["k"], uw["v"]],
                                              axis=1))
            elif "_q.w" in n:
                scope_f.set(n, uw["q"])
            elif "_o.w" in n or n.endswith("_output.w.0") \
                    or ".w" in n and "qkv" not in n and "_kv" not in n:
                # out-projection (and any remaining shared weight)
                src = [v for m, v in pu.items()
                       if np.shape(v) == np.shape(pf[n])
                       and ("_o" in m or m == n)]
                scope_f.set(n, src[0])

        got_f = []
        for _ in range(3):  # includes SGD updates: grads must match too
            out = exe.run(main_f, feed=feed, fetch_list=[loss_f])
            got_f.append(float(np.asarray(out[0])))

    with pt.scope_guard(scope_u):
        got_u = []
        for _ in range(3):
            out = exe.run(main_u, feed=feed, fetch_list=[loss_u])
            got_u.append(float(np.asarray(out[0])))
    return got_f, got_u


@pytest.mark.parametrize("cross", [False, True])
def test_fused_matches_unfused(cross):
    got_f, got_u = _losses_fused_and_unfused(cross)
    np.testing.assert_allclose(got_f, got_u, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("cross", [False, True])
@pytest.mark.parametrize("D,H,packed_kernel", [
    (128, 2, True),     # H*Dh 128 lanes: the short kernel reads it packed
    (32, 2, False)])    # 32 lanes: sliced in the op, the kernel on slices
def test_packed_attention_trains_as_the_unfused_one(cross, D, H,
                                                    packed_kernel):
    """Under the kernels (interpret mode) the fused program's
    flash_attention op takes the packed short kernel where H*Dh is a
    multiple of 128 lanes, and slices the projection in the op where it
    is not; three SGD steps give the unfused program's losses either
    way, and STATS["short_packed"] counts the packed traces only."""
    packed0, calls0 = fa.STATS["short_packed"], fa.STATS["pallas_calls"]
    fa.set_mode("interpret")
    try:
        got_f, got_u = _losses_fused_and_unfused(cross, T=8, D=D, H=H)
    finally:
        fa.set_mode("auto")
    np.testing.assert_allclose(got_f, got_u, rtol=1e-5, atol=1e-6)
    assert fa.STATS["pallas_calls"] > calls0
    assert (fa.STATS["short_packed"] > packed0) == packed_kernel


@pytest.mark.parametrize("cross", [False, True])
def test_fused_program_hands_the_projection_to_the_op_unsplit(cross):
    """With fused_qkv the program has no split: one flash_attention op
    takes the projection as its matmul wrote it (QKV, or Q and KV) with
    the `packed` and `n_head` attributes; the shape pass agrees with
    the declared [B, T, H*Dh] output, and the op survives the
    reference's ProgramDesc round trip and computes the same there."""
    from paddle_tpu.core import fluid_proto as fpr
    main, startup, loss = _build(True, cross=cross, train=False)
    ops = main.global_block().ops
    assert "split" not in [op.type for op in ops]
    attn = [op for op in ops if op.type == "flash_attention"]
    assert len(attn) == 1
    op = attn[0]
    packed = "kv" if cross else "qkv"
    assert op.attrs["packed"] == packed and op.attrs["n_head"] == H
    assert sorted(op.inputs) == (["KV", "Q"] if cross else ["QKV"])
    out = main.global_block().var(op.outputs["Out"][0])
    assert tuple(out.shape)[1:] == (T, D)
    assert not [d for d in main.verify(fetch_list=[loss.name])
                if d.pass_name == "shape-dtype"]

    feeds = ["q", "kv"] if cross else ["q"]
    blob = fpr.program_to_fluid(main, feed_names=feeds,
                                fetch_names=[loss.name])
    back, _, _ = fpr.program_from_fluid(blob)
    op2 = [o for o in back.global_block().ops
           if o.type == "flash_attention"][0]
    assert op2.inputs == op.inputs
    assert op2.attrs["packed"] == packed and op2.attrs["n_head"] == H

    rng = np.random.RandomState(1)
    feed = {n: rng.randn(B, T, D).astype("float32") for n in feeds}
    exe = pt.Executor(pt.CPUPlace())
    with pt.scope_guard(pt.Scope()):
        exe.run(startup)
        want = exe.run(main, feed=feed, fetch_list=[loss.name])[0]
        got = exe.run(back, feed=feed, fetch_list=[loss.name])[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-7)


def test_fused_layout_param_count():
    main_f, _, _ = _build(True)
    main_u, _, _ = _build(False)
    n_f = sum(int(np.prod(p.shape)) for p in main_f.all_parameters())
    n_u = sum(int(np.prod(p.shape)) for p in main_u.all_parameters())
    assert n_f == n_u
    names = [p.name for p in main_f.all_parameters()]
    assert any("_qkv" in n for n in names)


def test_explicit_unfused_keeps_reference_names():
    main, _, _ = _build(False)
    names = " ".join(p.name for p in main.all_parameters())
    for tag in ("_q.w", "_k.w", "_v.w"):
        assert tag in names


def test_convert_qkv_checkpoint_both_directions():
    """A checkpoint saved in either q/k/v layout loads into the other
    via convert_qkv_checkpoint with identical model outputs — the
    checkpoint-stability story behind the fused_qkv opt-in."""
    import paddle_tpu as pt
    from paddle_tpu.core import framework as fw, scope as sc
    from paddle_tpu.models import transformer as tfm

    T, B = 8, 4
    rng = np.random.RandomState(0)
    src = rng.randint(2, 30, (B, T)).astype("int64")
    feed = {"src": src, "src_len": np.full(B, T, "int64"),
            "trg": np.concatenate([np.zeros((B, 1), "int64"),
                                   src[:, :-1] + 1], 1),
            "trg_len": np.full(B, T, "int64")}

    def build_and_logits(fused, params=None):
        fw._main_program, fw._startup_program = fw.Program(), fw.Program()
        sc._global_scope = sc.Scope()
        cfg = tfm.TransformerConfig(
            src_vocab=32, trg_vocab=32, max_len=T, d_model=16,
            d_inner=32, n_head=2, n_layer=2, dropout=0.0,
            fused_qkv=fused)
        with pt.unique_name.guard():
            feeds, logits = tfm.build_infer_program(cfg, maxlen=T)
        exe = pt.Executor(pt.CPUPlace())
        exe.run(pt.default_startup_program())
        scope = pt.global_scope()
        if params is not None:
            for k, v in params.items():
                scope.set(k, v)
        names = [p.name for p in
                 pt.default_main_program().all_parameters()]
        vals = {n: np.asarray(scope.get(n)) for n in names}
        out = np.asarray(exe.run(feed=feed, fetch_list=[logits],
                                 is_test=True)[0])
        return cfg, vals, out

    cfg, unfused_params, ref_out = build_and_logits(fused=False)
    fused_params = tfm.convert_qkv_checkpoint(unfused_params, cfg,
                                              to_fused=True)
    assert any(k.endswith("qkv.w_0") for k in fused_params)
    _, _, fused_out = build_and_logits(fused=True, params=fused_params)
    np.testing.assert_allclose(fused_out, ref_out, rtol=1e-5, atol=1e-5)

    back = tfm.convert_qkv_checkpoint(fused_params, cfg, to_fused=False)
    assert set(back) == set(unfused_params)
    for k in back:
        np.testing.assert_allclose(back[k], unfused_params[k])
