"""Pallas flash-attention kernel tests (interpret mode on CPU).

Covers VERDICT r1 item 3: forward AND backward numerics vs the unfused
jnp reference (bias x causal grid), and proof that the kernel — not the
jnp fallback — is on the flagship transformer's training path under
jax.value_and_grad (trace-time counter + loss parity with the fallback).
"""
import inspect
import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as pt
from paddle_tpu.ops.pallas import flash_attention as fa


def _rand_qkv(rng, B=2, H=2, T=32, S=None, D=16):
    S = S or T
    q = jnp.asarray(rng.randn(B, H, T, D).astype("float32"))
    k = jnp.asarray(rng.randn(B, H, S, D).astype("float32"))
    v = jnp.asarray(rng.randn(B, H, S, D).astype("float32"))
    return q, k, v


def _pad_bias(rng, B, S):
    lens = rng.randint(S // 2, S + 1, (B,))
    mask = (np.arange(S)[None, :] < lens[:, None]).astype("float32")
    return jnp.asarray((mask - 1.0) * 1e9)     # 0 keep / -1e9 pad


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("with_bias", [False, True])
def test_flash_forward_matches_reference(causal, with_bias):
    rng = np.random.RandomState(0)
    q, k, v = _rand_qkv(rng)
    bias = _pad_bias(rng, q.shape[0], k.shape[2]) if with_bias else None
    out = fa.flash_attention(q, k, v, bias=bias, causal=causal,
                             interpret=True)
    ref = fa.flash_attention_reference(q, k, v, bias=bias, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_forward_cross_attention():
    """T != S (decoder cross-attention shape)."""
    rng = np.random.RandomState(1)
    q, k, v = _rand_qkv(rng, T=16, S=32)
    bias = _pad_bias(rng, 2, 32)
    out = fa.flash_attention(q, k, v, bias=bias, interpret=True)
    ref = fa.flash_attention_reference(q, k, v, bias=bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("with_bias", [False, True])
def test_flash_backward_matches_reference(causal, with_bias):
    rng = np.random.RandomState(2)
    q, k, v = _rand_qkv(rng)
    bias = _pad_bias(rng, q.shape[0], k.shape[2]) if with_bias else None
    g = jnp.asarray(rng.randn(*q.shape).astype("float32"))

    def loss_fa(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, bias=bias,
                                          causal=causal, interpret=True) * g)

    def loss_ref(q, k, v):
        return jnp.sum(fa.flash_attention_reference(q, k, v, bias=bias,
                                                    causal=causal) * g)

    dq, dk, dv = jax.grad(loss_fa, argnums=(0, 1, 2))(q, k, v)
    rq, rk, rv = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(rq),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(rk),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(rv),
                               rtol=1e-4, atol=1e-4)


def test_flash_multiblock_tiling():
    """Sequence longer than one block: online softmax across k blocks."""
    rng = np.random.RandomState(3)
    q, k, v = _rand_qkv(rng, B=1, H=1, T=64, D=8)
    out = fa.flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                             interpret=True)
    ref = fa.flash_attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def _train_transformer_loss(steps=2):
    """One tiny transformer Adam step sequence; returns losses."""
    from paddle_tpu.models import transformer as tfm
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        with pt.unique_name.guard():
            cfg = tfm.TransformerConfig(src_vocab=50, trg_vocab=50,
                                        max_len=16, d_model=32, d_inner=64,
                                        n_head=2, n_layer=1, dropout=0.0)
            feeds, avg_cost, tok = tfm.build_program(cfg, maxlen=16)
            pt.optimizer.Adam(1e-3).minimize(avg_cost)
    scope = pt.Scope()
    exe = pt.Executor(pt.CPUPlace())
    rng = np.random.RandomState(0)
    B, T = 4, 16
    losses = []
    with pt.scope_guard(scope):
        exe.run(startup)
        for i in range(steps):
            src = rng.randint(3, cfg.src_vocab, (B, T)).astype("int64")
            trg = np.concatenate([np.zeros((B, 1), "int64"),
                                  (src[:, :-1] + 1) % cfg.trg_vocab],
                                 axis=1)
            out = exe.run(main, feed={
                "src": src, "src_len": np.full(B, T, "int64"),
                "trg": trg, "trg_len": np.full(B, T, "int64"),
                "label": (src + 1) % cfg.trg_vocab},
                fetch_list=[avg_cost])
            losses.append(float(out[0]))
    return losses


def test_flash_active_on_transformer_training_path():
    """The Pallas kernel (not the fallback) runs under value_and_grad on
    the flagship model, and its training numerics match the fallback."""
    before = fa.STATS["pallas_calls"]
    fa.set_mode("interpret")
    try:
        losses_flash = _train_transformer_loss()
    finally:
        fa.set_mode("auto")
    calls = fa.STATS["pallas_calls"] - before
    # 1 enc self + 1 dec self + 1 dec cross per layer, traced fwd + replay
    assert calls >= 3, f"flash kernel not traced ({calls} calls)"
    assert np.isfinite(losses_flash).all()

    # same seeds, jnp fallback path → numerics must agree
    fa.set_mode("off")
    try:
        losses_ref = _train_transformer_loss()
    finally:
        fa.set_mode("auto")
    np.testing.assert_allclose(losses_flash, losses_ref, rtol=2e-4,
                               atol=2e-4)


def test_flash_causal_cross_shape_matches_reference():
    """Causal with T != S must use the bottom-right-aligned diagonal
    (jnp.tril k=S-T), matching the XLA fallback — the same op must not
    change semantics across the MIN_SEQ_LEN dispatch gate."""
    rng = np.random.RandomState(7)
    q, k, v = _rand_qkv(rng, T=16, S=32)
    out = fa.flash_attention(q, k, v, causal=True, interpret=True)
    ref = fa.flash_attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # grads too (block-skip predicate shares the offset)
    def loss(fn):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v).astype(jnp.float32) ** 2)
    g = jax.grad(loss(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, interpret=True)), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(lambda q, k, v: fa.flash_attention_reference(
        q, k, v, causal=True)), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


def test_flash_supports_non_default_block_multiples():
    """Sequence lengths that are 8/128-multiples but don't divide the
    tuned 512/1024 defaults must stay on the Pallas path (they are
    exactly the long sequences the unfused path cannot handle)."""
    rng = np.random.RandomState(8)
    q, k, v = _rand_qkv(rng, T=24, S=40)   # 8-multiples, not 512/1024
    assert fa.supports(q, k, v)
    out = fa.flash_attention(q, k, v, interpret=True)
    ref = fa.flash_attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    assert fa._pick_block(16512, 1024) == 384   # 43 x 384
    # the downward 128-multiple scan finds 384 (the halving loop it
    # replaced could only reach 256 — or illegal non-multiples like 960)
    assert fa._pick_block(768, 512) == 384
    assert fa._pick_block(1920, 960) == 640
    # VMEM clamp keeps wide-head long-seq shapes legal AND in budget
    bq, bk = fa._choose_blocks(4096, 1920, 128, 128)
    assert bq * bk <= 1024 * 1024 and 4096 % bq == 0 and 1920 % bk == 0
    # lane dims that are neither 128-multiples nor the full axis are not
    # legal Mosaic tiles — supports() must refuse them (hardware-only
    # failure; interpret mode can't catch it)
    assert fa._pick_block(4160, 1024) == 0
    q2, k2, v2 = _rand_qkv(rng, T=128, S=4160, D=16)
    assert not fa.supports(q2, k2, v2)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_bf16_softmax_close_to_reference(causal):
    """softmax_dtype=bf16 (the VPU-pressure escape): fwd and bwd must
    stay within bf16-exp tolerance of the f32 reference."""
    import jax
    import jax.numpy as jnp
    rng = np.random.RandomState(21)
    q, k, v = _rand_qkv(rng, T=16, S=16)
    out = fa.flash_attention(q, k, v, causal=causal, interpret=True,
                             softmax_dtype=jnp.bfloat16)
    ref = fa.flash_attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)

    def loss(q, k, v):
        o = fa.flash_attention(q, k, v, causal=causal, interpret=True,
                               softmax_dtype=jnp.bfloat16)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    def loss_ref(q, k, v):
        o = fa.flash_attention_reference(q, k, v, causal=causal)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-2, atol=5e-2)


def test_flash_softmax_dtype_global_knob():
    import jax.numpy as jnp
    rng = np.random.RandomState(22)
    q, k, v = _rand_qkv(rng, T=16, S=16)
    try:
        fa.set_softmax_dtype(jnp.bfloat16)
        out = fa.flash_attention(q, k, v, interpret=True)
    finally:
        fa.set_softmax_dtype(jnp.float32)
    ref = fa.flash_attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)
    # knob restored: default path is exact-tolerance again
    out2 = fa.flash_attention(q, k, v, interpret=True)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# the tiled backward: one kernel that rebuilds the probabilities once
# ---------------------------------------------------------------------------
_BWD_CASES = {
    # name: (causal, with_bias, group, T, S, causal_offset, lse cotangent)
    "full": (False, False, 1, 64, 64, 0, False),
    "full_bias_group4": (False, True, 4, 64, 64, 0, False),
    "full_cross_bias_lse": (False, True, 1, 32, 64, 0, True),
    "causal": (True, False, 1, 64, 64, 0, False),
    "causal_bias": (True, True, 1, 64, 64, 0, False),
    "causal_group4": (True, False, 4, 64, 64, 0, False),
    "causal_group4_bias_lse": (True, True, 4, 64, 64, 0, True),
    "causal_cross_bottom_right": (True, False, 1, 64, 128, 0, False),
    "causal_cross_group4_bias": (True, True, 4, 32, 96, 0, False),
    "causal_strict_lse": (True, False, 1, 64, 64, -1, True),
    "causal_strict_group4": (True, False, 4, 64, 64, -1, False),
    "causal_strict_cross_group4_bias_lse": (True, True, 4, 64, 128, -1, True),
}


def _attend_with_lse_reference(q, k, v, bias, causal, causal_offset):
    """(out, lse) of the unfused composition in float32, grouped heads
    and a shifted bottom-right diagonal included."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
    if bias is not None:
        s = s + bias[:, None, None, :]
    if causal:
        T, S = s.shape[-2:]
        s = jnp.where(jnp.tril(jnp.ones((T, S), bool),
                               k=S - T + causal_offset), s, -1e30)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", jnp.exp(s - lse[..., None]), v), lse


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(_BWD_CASES))
def test_tiled_backward_is_one_kernel_and_matches(case, dtype, monkeypatch):
    """dq, dk, dv (and db) of flash_attention_bwd, the one backward
    kernel, against the unfused reference and against the two kernels it
    replaced (which the same shapes take when nothing fits the VMEM
    budget), at blocks of 16 x 32 so that blocks above, on and wholly
    under the diagonal all occur; STATS says which ran."""
    causal, with_bias, group, T, S, offset, with_lse = _BWD_CASES[case]
    dt = jnp.dtype(dtype)
    B, KVH, D = 2, 2 if group == 1 else 1, 16
    rng = np.random.RandomState(33)
    q = jnp.asarray(rng.randn(B, KVH * group, T, D), dt)
    k = jnp.asarray(rng.randn(B, KVH, S, D), dt)
    v = jnp.asarray(rng.randn(B, KVH, S, D), dt)
    w = rng.randn(*q.shape).astype("float32")
    u = rng.randn(*q.shape[:3]).astype("float32") * with_lse
    if offset < 0:
        # a strict triangle leaves the first query no key: its row is
        # the caller's to weight to zero (ring attention's merge does)
        w[:, :, :-offset], u[:, :, :-offset] = 0.0, 0.0
    w, u = jnp.asarray(w), jnp.asarray(u)
    bias = _pad_bias(rng, B, S) if with_bias else None

    def tiled(q, k, v, b):
        kw = dict(bias=b, causal=causal, block_q=16, block_k=32,
                  interpret=True, causal_offset=offset)
        if with_lse:
            return fa.flash_attention_with_lse(q, k, v, **kw)
        return fa.flash_attention(q, k, v, **kw), 0.0

    def ref(q, k, v, b):
        return _attend_with_lse_reference(q, k, v, b, causal, offset)

    def loss(fn):
        def f(q, k, v, b):
            out, lse = fn(q, k, v, b)
            return jnp.sum(out.astype(jnp.float32) * w) + jnp.sum(lse * u)
        return jax.grad(f, argnums=(0, 1, 2, 3) if with_bias else (0, 1, 2))

    before = dict(fa.STATS)
    fused = loss(tiled)(q, k, v, bias)
    assert fa.STATS["tiled_bwd_fused"] == before["tiled_bwd_fused"] + 1
    assert fa.STATS["tiled_bwd_split"] == before["tiled_bwd_split"]
    monkeypatch.setattr(fa, "FUSED_BWD_VMEM", 0)
    split = loss(tiled)(q, k, v, bias)
    assert fa.STATS["tiled_bwd_split"] == before["tiled_bwd_split"] + 1
    want = loss(ref)(q, k, v, bias)
    tol = dict(rtol=1e-4, atol=1e-4) if dtype == "float32" \
        else dict(rtol=3e-2, atol=3e-2)
    same = dict(rtol=1e-6, atol=1e-6) if dtype == "float32" \
        else dict(rtol=1e-2, atol=1e-2)
    for name, a, b, c in zip(("dq", "dk", "dv", "db"), fused, split, want):
        assert a.shape == c.shape and a.dtype == b.dtype, name
        a, b, c = (np.asarray(x, np.float32) for x in (a, b, c))
        np.testing.assert_allclose(a, c, err_msg=name, **tol)
        np.testing.assert_allclose(a, b, err_msg=name, **same)


def test_tiled_backward_over_the_vmem_budget_is_two_kernels():
    """Which backward runs is a function of the shapes alone: where the
    dq of a key-value head's query heads ([group * T, D] float32 and the
    output's two buffers, 128 lanes wide in VMEM) is over FUSED_BWD_VMEM,
    the trace holds flash_attention_dq and flash_attention_dkv; at the
    benchmark cell's shape, and one power of two under the budget, it
    holds flash_attention_bwd. Traced, not run."""
    def kernels(H, KVH, T, D, dtype):
        q = jax.ShapeDtypeStruct((1, H, T, D), dtype)
        kv = jax.ShapeDtypeStruct((1, KVH, T, D), dtype)
        before = dict(fa.STATS)
        text = str(jax.make_jaxpr(jax.grad(
            lambda q, k, v: fa.flash_attention(
                q, k, v, causal=True, interpret=True).astype(
                    jnp.float32).sum(), argnums=(0, 1, 2)))(q, kv, kv))
        names = sorted({n for n in ("flash_attention_bwd",
                                    "flash_attention_dq",
                                    "flash_attention_dkv") if n in text})
        return names, {key: fa.STATS[key] - before[key]
                       for key in ("tiled_bwd_fused", "tiled_bwd_split")}

    assert fa._bwd_resident_bytes(4, 8192, 64, 2) == 32 * 1024 * 1024
    assert kernels(32, 8, 8192, 64, jnp.bfloat16) == (
        ["flash_attention_bwd"], {"tiled_bwd_fused": 1, "tiled_bwd_split": 0})
    assert kernels(1, 1, 65536, 128, jnp.bfloat16) == (
        ["flash_attention_bwd"], {"tiled_bwd_fused": 1, "tiled_bwd_split": 0})
    assert kernels(1, 1, 131072, 128, jnp.bfloat16) == (
        ["flash_attention_dkv", "flash_attention_dq"],
        {"tiled_bwd_fused": 0, "tiled_bwd_split": 1})
    assert kernels(8, 2, 32768, 64, jnp.float32)[0] == [
        "flash_attention_dkv", "flash_attention_dq"]


def test_causal_key_blocks_follow_the_sweep():
    """_choose_blocks: 1024 keys a block under a causal diagonal, 2048
    without one (the sweep above DEFAULT_BLOCK_Q); a caller's own blocks
    win; what is legal does not depend on `causal`."""
    assert fa._choose_blocks(8192, 8192, 64, 64) == (1024, 2048)
    assert fa._choose_blocks(8192, 8192, 64, 64, causal=True) == (1024, 1024)
    assert fa._choose_blocks(8192, 8192, 64, 64, 512, 2048, True) \
        == (512, 2048)
    # 1100 keys: one block of the whole axis either way
    assert fa._choose_blocks(128, 1100, 64, 64, causal=True) == (128, 1100)
    assert fa._choose_blocks(128, 1100, 64, 64) == (128, 1100)


# ---------------------------------------------------------------------------
# a sliding window: the band of blocks, forward and the one backward kernel
# ---------------------------------------------------------------------------
_WINDOW_CASES = {
    # name: (T, S, window, group, block_q, block_k, with_bias)
    "smaller_than_a_block": (384, 384, 64, 1, 128, 128, False),
    "equal_to_a_block": (384, 384, 128, 2, 128, 128, False),
    "not_a_multiple_of_a_block": (384, 384, 200, 2, 128, 128, True),
    "larger_than_a_block_group4": (512, 512, 300, 4, 128, 256, False),
    "two_k_blocks_a_q_block": (512, 512, 256, 1, 256, 128, True),
    "one_key": (256, 256, 1, 2, 128, 128, False),
    "cross_bottom_right": (256, 512, 130, 2, 128, 128, False),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(_WINDOW_CASES))
def test_windowed_kernels_match_the_masked_composition(case, dtype,
                                                        monkeypatch):
    """flash_attention_win_fwd / _win_bwd in the interpreter against the
    unfused composition with the band as an explicit mask: the output and
    dq, dk, dv (and db), with the window smaller than, equal to, not a
    multiple of and larger than a block, grouped heads, and a key length
    over the query's; the two kernels the backward falls to over the VMEM
    budget take the same band."""
    T, S, window, group, bq, bk, with_bias = _WINDOW_CASES[case]
    dt = jnp.dtype(dtype)
    B, KVH, D = 2, 2 if group == 1 else 1, 16
    rng = np.random.RandomState(36)
    q = jnp.asarray(rng.randn(B, KVH * group, T, D), dt)
    k = jnp.asarray(rng.randn(B, KVH, S, D), dt)
    v = jnp.asarray(rng.randn(B, KVH, S, D), dt)
    w = jnp.asarray(rng.randn(*q.shape).astype("float32"))
    # every seventh key padded away, whatever window it falls in (no row
    # is left without a key: the windows here are 200 and 256 wide)
    bias = jnp.where(jnp.arange(S)[None] % 7 == 3, -1e9, 0.0) \
        * jnp.ones((B, 1)) if with_bias else None

    def tiled(q, k, v, b):
        return fa.flash_attention(q, k, v, bias=b, causal=True,
                                  window=window, block_q=bq, block_k=bk,
                                  interpret=True)

    def ref(q, k, v, b):
        return fa.flash_attention_reference(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), b, causal=True, window=window)

    def both(fn):
        def f(q, k, v, b):
            out = fn(q, k, v, b)
            return jnp.sum(out.astype(jnp.float32) * w), out
        return jax.value_and_grad(
            f, argnums=(0, 1, 2, 3) if with_bias else (0, 1, 2),
            has_aux=True)

    before = dict(fa.STATS)
    (_, out), fused = both(tiled)(q, k, v, bias)
    assert fa.STATS["tiled_window"] == before["tiled_window"] + 1
    assert fa.STATS["tiled_bwd_fused"] == before["tiled_bwd_fused"] + 1
    monkeypatch.setattr(fa, "FUSED_BWD_VMEM", 0)
    (_, _), split = both(tiled)(q, k, v, bias)
    assert fa.STATS["tiled_bwd_split"] == before["tiled_bwd_split"] + 1
    assert fa.STATS["tiled_window"] == before["tiled_window"] + 2
    (_, want_out), want = both(ref)(q, k, v, bias)
    tol = dict(rtol=1e-4, atol=1e-4) if dtype == "float32" \
        else dict(rtol=3e-2, atol=3e-2)
    same = dict(rtol=1e-6, atol=1e-6) if dtype == "float32" \
        else dict(rtol=1e-2, atol=1e-2)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want_out), **tol)
    for name, a, b, c in zip(("dq", "dk", "dv", "db"), fused, split, want):
        assert a.shape == c.shape, name
        a, b, c = (np.asarray(x, np.float32) for x in (a, b, c))
        np.testing.assert_allclose(a, c, err_msg=name, **tol)
        np.testing.assert_allclose(a, b, err_msg=name, **same)


def test_the_band_is_what_the_windowed_grid_walks():
    """The inner grid axis of a windowed call is the band's blocks only,
    the kernels carry names of their own, and a call with no window (or
    one at or over the key length) traces to the kernels, the names, the
    grid and the STATS it had."""
    sds = jax.ShapeDtypeStruct
    q = sds((1, 8, 2048, 64), jnp.float32)
    kv = sds((1, 2, 2048, 64), jnp.float32)

    def traced(**kw):
        before = dict(fa.STATS)
        text = str(jax.make_jaxpr(jax.grad(
            lambda q, k, v: fa.flash_attention(
                q, k, v, causal=True, block_q=256, block_k=256,
                interpret=True, **kw).sum(), argnums=(0, 1, 2)))(q, kv, kv))
        names = sorted(set(re.findall(r"flash_attention_\w+", text)))
        grids = re.findall(r"grid=\((\d+), (\d+), (\d+)\)", text)
        return names, sorted(set(grids)), {
            key: fa.STATS[key] - before[key] for key in before}

    plain = traced()
    assert plain[0] == ["flash_attention_bwd", "flash_attention_fwd"]
    assert plain[1] == [("2", "8", "32"), ("8", "8", "8")]
    assert plain[2]["tiled_window"] == 0 and plain[2]["tiled_bwd_fused"] == 1
    assert traced(window=None) == plain and traced(window=2048) == plain
    assert traced(window=5000) == plain
    names, grids, stats = traced(window=300)
    assert names == ["flash_attention_win_bwd", "flash_attention_win_fwd"]
    # 300 keys under 256 x 256 blocks: three k blocks a q block, three q
    # blocks a k block (times the group's four heads), of eight
    assert grids == [("2", "8", "12"), ("8", "8", "3")]
    assert stats["tiled_window"] == 1 and stats["tiled_bwd_fused"] == 1
    assert traced(window=256)[1] == [("2", "8", "8"), ("8", "8", "2")]
    # the block ranges, by hand at the mellum2 cell's shape: window 1024
    # over 1024 x 1024 blocks touches 15 blocks a head where the causal
    # half touches 36
    at = (1024, 1024, 0)
    band = [(fa._first_k(i, *at, 1024), fa._last_k(i, *at, 8)) for i in
            range(8)]
    assert band == [(0, 0)] + [(i - 1, i) for i in range(1, 8)]
    assert sum(b - a + 1 for a, b in band) == 15
    assert sum(fa._last_k(i, *at, 8) + 1 for i in range(8)) == 36
    assert [(fa._first_q(j, *at, 8), fa._last_q(j, *at, 1024, 8))
            for j in range(8)] == [(j, min(j + 1, 7)) for j in range(8)]
    assert fa._k_steps(8, 8, 1024, 1024, 0, 1024) == 2
    assert fa._k_steps(16, 16, 512, 512, 0, 1024) == 3


def test_try_flash_alone_decides_the_windowed_path():
    """A window goes to the tiled kernel where it takes the lengths, and
    to the caller's composition (None) where it does not: a length off a
    multiple of the block, the lse, a shifted or missing diagonal; the
    short kernel never takes one; the op's composition masks the same
    band."""
    rng = np.random.RandomState(7)

    def qkv(T, H=4, KVH=2, D=16):
        return (jnp.asarray(rng.randn(1, T, H, D), jnp.float32),
                jnp.asarray(rng.randn(1, T, KVH, D), jnp.float32),
                jnp.asarray(rng.randn(1, T, KVH, D), jnp.float32))

    q, k, v = qkv(256)
    want = fa.flash_attention_reference(q, k, v, causal=True, window=40,
                                        layout="bthd")
    assert fa.try_flash(q, k, v, causal=True, layout="bthd",
                        window=40) is None            # the CPU: no kernel
    fa.set_mode("interpret")
    try:
        got = fa.try_flash(q, k, v, causal=True, layout="bthd", window=40)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)
        same = qkv(256, KVH=4)      # the short kernel's: heads not shared
        assert not fa.picks_short(*same, layout="bthd", interpret=True,
                                  window=40)
        assert fa.picks_short(*same, layout="bthd", interpret=True)
        for kw in (dict(with_lse=True), dict(causal_offset=-1)):
            assert fa.try_flash(q, k, v, causal=True, layout="bthd",
                                window=40, **kw) is None
        assert fa.try_flash(q, k, v, causal=False, layout="bthd",
                            window=40) is None
        # 1100 is over one block and no multiple of a legal one: the
        # composition's
        q3, k3, v3 = qkv(1100)
        assert fa.try_flash(q3, k3, v3, causal=True, layout="bthd",
                            window=40) is None
    finally:
        fa.set_mode("auto")
    from paddle_tpu.ops import kernels_nn
    out = kernels_nn._sdpa(None, {"Q": [q3], "K": [k3], "V": [v3]},
                           {"layout": "bthd", "causal": True, "window": 40,
                            "scale": 0.25})["Out"][0]
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(fa.flash_attention_reference(
            q3, k3, v3, causal=True, window=40, layout="bthd")),
        atol=2e-5, rtol=2e-5)
    with pytest.raises(NotImplementedError):
        fa.flash_attention(q.swapaxes(1, 2), k.swapaxes(1, 2),
                           v.swapaxes(1, 2), window=40, interpret=True)


def test_a_windowed_call_keeps_the_causal_blocks():
    """The band's sweep found the causal pair again, so a window is no
    argument of the block policy; at head 128 the pair fills the budget."""
    assert fa._choose_blocks(8192, 8192, 128, 128, causal=True) \
        == (1024, 1024)
    assert fa._choose_blocks(8192, 8192, 128, 128, 512, 256, True) \
        == (512, 256)
    assert "window" not in inspect.signature(fa._choose_blocks).parameters


# ---------------------------------------------------------------------------
# the short-sequence kernel: the op's own [B, T, H, D] layout, one tile
# ---------------------------------------------------------------------------
_SHORT_CASES = {
    # name: (B, T, S, H, D, causal, with_bias)
    "self": (2, 256, 256, 8, 64, False, False),
    "causal_self_bias": (2, 256, 256, 8, 64, True, True),
    "cross": (2, 256, 128, 8, 64, False, True),
    "cross_ragged_keys": (2, 128, 200, 8, 64, False, True),
    "ragged_queries": (1, 72, 72, 4, 32, True, True),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(_SHORT_CASES))
def test_short_kernel_matches_reference(case, dtype):
    """flash_attention_bthd (interpret mode) against the unfused
    reference in float32 on the same (rounded) inputs: the output and
    the gradients of q, k, v; where there is a bias, its gradient too."""
    B, T, S, H, D, causal, with_bias = _SHORT_CASES[case]
    dt = jnp.dtype(dtype)
    rng = np.random.RandomState(31)
    q = jnp.asarray(rng.randn(B, T, H, D), dt)
    k = jnp.asarray(rng.randn(B, S, H, D), dt)
    v = jnp.asarray(rng.randn(B, S, H, D), dt)
    w = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
    bias = _pad_bias(rng, B, S).reshape(B, 1, 1, S) if with_bias else None
    assert fa.supports_short(q, k, v, bias)

    def short(q, k, v, b):
        return fa.flash_attention_bthd(q, k, v, bias=b, causal=causal,
                                       interpret=True)

    def ref(q, k, v, b):
        f32 = [x.astype(jnp.float32) for x in (q, k, v)]
        return fa.flash_attention_reference(*f32, bias=b, causal=causal,
                                            layout="bthd")

    def loss(fn):
        return lambda q, k, v, b: jnp.sum(
            fn(q, k, v, b).astype(jnp.float32) * w)

    tol = dict(rtol=2e-5, atol=2e-5) if dtype == "float32" \
        else dict(rtol=2e-2, atol=2e-2)
    out = short(q, k, v, bias)
    assert out.shape == (B, T, H, D) and out.dtype == dt
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref(q, k, v, bias)), **tol)
    argnums = (0, 1, 2, 3) if with_bias else (0, 1, 2)
    g = jax.grad(loss(short), argnums=argnums)(q, k, v, bias)
    gr = jax.grad(loss(ref), argnums=argnums)(q, k, v, bias)
    gtol = dict(rtol=1e-4, atol=1e-4) if dtype == "float32" \
        else dict(rtol=3e-2, atol=3e-2)
    for a, b in zip(g, gr):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), **gtol)


_PACKED_CASES = {
    # name: (packed, B, T, S, H, D, causal); a key-padding bias in each
    "qkv": ("qkv", 2, 64, 64, 2, 64, False),
    "qkv_causal": ("qkv", 2, 64, 64, 2, 64, True),
    "kv": ("kv", 2, 64, 48, 2, 64, False),
}

# what the transpose of a split, a slice or a concatenation leaves in a
# jaxpr: none of it may touch the packed arrays
_COPIES = ("split", "slice", "pad", "concatenate", "dynamic_slice",
           "dynamic_update_slice")


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(_PACKED_CASES))
def test_packed_short_kernel_equals_the_split_one(case, dtype):
    """flash_attention_packed (interpret mode) on the fused projection
    against flash_attention_bthd on its slices: the output, and the
    gradient of each packed array against the split path's gradients
    side by side, equal to the last bit (the arithmetic is the same).
    Its calls keep the short kernel's names, it is counted in
    STATS["short_packed"], and its gradient's jaxpr slices, pads and
    concatenates nothing."""
    packed, B, T, S, H, D, causal = _PACKED_CASES[case]
    dt, HD = jnp.dtype(dtype), H * D
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(B, T, (3 if packed == "qkv" else 1) * HD), dt)
    kv = jnp.asarray(rng.randn(B, S, 2 * HD), dt) if packed == "kv" else x
    w = jnp.asarray(rng.randn(B, T, HD), jnp.float32)
    bias = _pad_bias(rng, B, S).reshape(B, 1, 1, S)

    def packed_fn(x, kv):
        return fa.flash_attention_packed(x, kv, kv, packed, H, bias=bias,
                                         causal=causal, interpret=True)

    def split_fn(x, kv):
        q, k, v = fa.unpack(x, kv, kv, packed, H)
        assert q.shape == (B, T, H, D) and k.shape == (B, S, H, D)
        return fa.flash_attention_bthd(q, k, v, bias=bias, causal=causal,
                                       interpret=True).reshape(B, T, HD)

    def loss(fn):
        if packed == "qkv":
            return lambda x: jnp.sum(fn(x, x).astype(jnp.float32) * w)
        return lambda x, kv: jnp.sum(fn(x, kv).astype(jnp.float32) * w)

    args = (x,) if packed == "qkv" else (x, kv)
    argnums = tuple(range(len(args)))
    counted = fa.STATS["short_packed"]
    out = packed_fn(x, kv)
    assert fa.STATS["short_packed"] == counted + 1
    assert out.shape == (B, T, HD) and out.dtype == dt
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(split_fn(x, kv), np.float32))
    grads = jax.grad(loss(packed_fn), argnums)(*args)
    want = jax.grad(loss(split_fn), argnums)(*args)
    for got, ref, a in zip(grads, want, args):
        assert got.shape == a.shape and got.dtype == dt
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(ref, np.float32))
    jaxpr = jax.make_jaxpr(jax.grad(loss(packed_fn), argnums))(*args)
    text = str(jaxpr)
    assert "flash_attention_short_fwd" in text
    assert "flash_attention_short_bwd" in text
    prims = {e.primitive.name for e in jaxpr.jaxpr.eqns}
    assert not prims & set(_COPIES), prims


def test_try_flash_takes_a_packed_call_by_the_short_kernels_policy():
    """A packed call goes to the packed short kernel where picks_short
    takes its segments and they are whole 128-lane vregs; anywhere else
    try_flash slices it and it goes where `bthd` arrays go (the tiled
    kernel, or None for the composition), its result [B, T, H*D]."""
    from paddle_tpu.ops.registry import lowering_for
    sds = jax.ShapeDtypeStruct

    def picked(*a, **kw):
        text = str(jax.make_jaxpr(lambda *a: fa.try_flash(*a, **kw))(*a))
        return [n for n in ("flash_attention_short_fwd",
                            "flash_attention_fwd") if n in text]

    qkv = sds((4, 256, 3 * 512), jnp.bfloat16)
    q, kv = sds((4, 256, 512), jnp.bfloat16), sds((4, 384, 1024),
                                                  jnp.bfloat16)
    with lowering_for("tpu"):
        counted = fa.STATS["short_packed"]
        assert picked(qkv, qkv, qkv, causal=True, layout="bthd",
                      packed="qkv", n_heads=8) \
            == ["flash_attention_short_fwd"]
        assert picked(q, kv, kv, layout="bthd", packed="kv", n_heads=8) \
            == ["flash_attention_short_fwd"]
        assert fa.STATS["short_packed"] == counted + 2
        # 8 heads of 16: 128 lanes a segment, a block the index map can
        # pick; 4 heads of 16 are 64 lanes: the short kernel on slices
        narrow = sds((4, 256, 3 * 64), jnp.bfloat16)
        assert fa.picks_packed(*(sds((4, 256, 3 * 128), jnp.bfloat16),)
                               * 3, "qkv", 8)
        assert not fa.picks_packed(narrow, narrow, narrow, "qkv", 4)
        assert fa.picks_short(*fa.packed_segments(narrow, narrow, narrow,
                                                  "qkv", 4),
                              layout="bthd", interpret=True)
        # past the short kernel's lengths: sliced, then the tiled kernel
        long = sds((1, fa.MIN_SEQ_LEN_BTHD, 3 * 512), jnp.bfloat16)
        assert picked(long, long, long, layout="bthd", packed="qkv",
                      n_heads=8) == ["flash_attention_fwd"]
        out = jax.eval_shape(lambda x: fa.try_flash(
            x, x, x, layout="bthd", packed="qkv", n_heads=8), long)
        assert out.shape == (1, fa.MIN_SEQ_LEN_BTHD, 512)
        # under the measured range: no kernel, the op's composition
        short = sds((4, 128, 3 * 512), jnp.bfloat16)
        assert jax.eval_shape(lambda x: fa.try_flash(
            x, x, x, layout="bthd", packed="qkv", n_heads=8), short) is None
        assert fa.STATS["short_packed"] == counted + 2
    with lowering_for("cpu"):
        assert fa.try_flash(qkv, qkv, qkv, layout="bthd", packed="qkv",
                            n_heads=8) is None


def test_try_flash_picks_the_short_kernel_by_layout_and_length():
    """The one policy: `bthd` arrays at a key length the short kernel
    takes go to it (no transpose); `bhtd` arrays and `with_lse` callers
    keep the tiled kernel's gate."""
    from paddle_tpu.ops.registry import lowering_for
    sds = jax.ShapeDtypeStruct
    q = sds((4, 256, 8, 64), jnp.bfloat16)
    qt = sds((4, 8, 256, 64), jnp.bfloat16)

    def picked(fn, *a):
        jaxpr = jax.make_jaxpr(fn)(*a)
        text = str(jaxpr)
        return [n for n in ("flash_attention_short_fwd",
                            "flash_attention_fwd") if n in text], \
            [e.primitive.name for e in jaxpr.jaxpr.eqns]

    with lowering_for("tpu"):
        names, prims = picked(lambda q, k, v: fa.try_flash(
            q, k, v, causal=True, layout="bthd"), q, q, q)
        assert names == ["flash_attention_short_fwd"]
        assert "transpose" not in prims
        # bhtd at 256 and a with_lse caller: the tiled kernel's gates,
        # so XLA's composition
        assert fa.try_flash(qt, qt, qt) is None
        assert fa.try_flash(qt, qt, qt, with_lse=True) is None
        assert fa.try_flash(q, q, q, with_lse=True, layout="bthd") is None
        # under the measured range (a tie at 128), and at lengths Mosaic
        # has not compiled the kernel for (264 keys, 320 x 264): the
        # composition
        for t, s_ in ((128, 128), (264, 264), (320, 264), (256, 264)):
            qq = sds((4, t, 8, 64), jnp.bfloat16)
            kk = sds((4, s_, 8, 64), jnp.bfloat16)
            assert fa.try_flash(qq, kk, kk, layout="bthd") is None, (t, s_)
        mid = sds((4, 384, 8, 64), jnp.bfloat16)
        assert picked(lambda q, k, v: fa.try_flash(
            q, k, v, layout="bthd"), mid, q, q)[0] \
            == ["flash_attention_short_fwd"]
        # past the short kernel's lengths the op's bthd arrays go to the
        # tiled kernel from the length the chip showed; bhtd callers
        # (ulysses, ring attention) keep the gate they had
        assert fa.SHORT_MAX_SEQ_LEN < fa.MIN_SEQ_LEN_BTHD < fa.MIN_SEQ_LEN
        assert fa.MIN_SEQ_LEN == 4096
        long_q = sds((1, fa.MIN_SEQ_LEN_BTHD, 8, 64), jnp.bfloat16)
        names, prims = picked(lambda q, k, v: fa.try_flash(
            q, k, v, layout="bthd"), long_q, long_q, long_q)
        assert names == ["flash_attention_fwd"] and "transpose" in prims
        long_t = sds((1, 8, fa.MIN_SEQ_LEN_BTHD, 64), jnp.bfloat16)
        assert fa.try_flash(long_t, long_t, long_t) is None
        assert fa.try_flash(long_t, long_t, long_t, with_lse=True) is None
        assert fa.try_flash(long_q, long_q, long_q, with_lse=True,
                            layout="bthd") is None
        lse_q = sds((1, 8, fa.MIN_SEQ_LEN, 64), jnp.bfloat16)
        assert picked(lambda q, k, v: fa.try_flash(q, k, v),
                      lse_q, lse_q, lse_q)[0] == ["flash_attention_fwd"]
        out, lse = jax.eval_shape(lambda q, k, v: fa.try_flash(
            q, k, v, with_lse=True), lse_q, lse_q, lse_q)
        assert lse.shape == lse_q.shape[:3]
    with lowering_for("cpu"):
        assert fa.try_flash(q, q, q, layout="bthd") is None


def _jaxpr_eqns(jaxpr, skip=("pallas_call",)):
    """Every equation of a jaxpr and its sub-jaxprs, a kernel's own body
    left out."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name in skip:
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _jaxpr_eqns(sub, skip)


def _jaxpr_avals(jaxpr):
    """Every intermediate's aval in a jaxpr and its sub-jaxprs, a
    kernel's own body left out."""
    for eqn in _jaxpr_eqns(jaxpr):
        for var in eqn.outvars:
            yield var.aval


def test_train_step_at_the_cells_widths_keeps_no_scores_tensor():
    """A traced transformer train step at transformer-base's widths (two
    layers): 3 accepted flash_attention dispatches a layer, none
    rejected, and no [B, H, T, S] array anywhere in the step's jaxpr,
    so neither among the forward's outputs nor the backward's
    residuals."""
    from paddle_tpu.core.trace import build_step_fn
    from paddle_tpu.models import transformer as tfm
    from paddle_tpu.ops import kern
    from paddle_tpu.ops.registry import lowering_for
    B, T, L = 2, 256, 2
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        with pt.unique_name.guard():
            cfg = tfm.TransformerConfig(
                src_vocab=512, trg_vocab=512, max_len=T, d_model=512,
                d_inner=2048, n_head=8, n_layer=L, dropout=0.0,
                fused_qkv=True)
            feeds, avg_cost, tok = tfm.build_program(cfg, maxlen=T)
            pt.optimizer.Adam(1e-3).minimize(avg_cost)
    pt.amp.cast_program_to_bf16(main)
    scope = pt.Scope()
    with pt.scope_guard(scope):
        pt.Executor(pt.CPUPlace()).run(startup)
    persist = {v.name: jax.ShapeDtypeStruct(scope.get(v.name).shape,
                                            scope.get(v.name).dtype)
               for v in main.persistable_vars()}
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)      # noqa: E731
    feed = {"src": i32(B, T), "trg": i32(B, T), "label": i32(B, T),
            "src_len": i32(B), "trg_len": i32(B)}
    step = build_step_fn(main, [avg_cost.name], False, None)
    per = kern.STATS["by_kernel"].setdefault(
        "flash_attention", {"accepted": 0, "rejected": 0})
    before, packed0 = dict(per), fa.STATS["short_packed"]
    with lowering_for("tpu"):
        jaxpr = jax.make_jaxpr(step)(persist, feed, jax.random.PRNGKey(0))
    assert per["accepted"] - before["accepted"] == 3 * L
    assert per["rejected"] == before["rejected"]
    assert fa.STATS["short_packed"] - packed0 == 3 * L
    text = str(jaxpr)
    assert "flash_attention_short_fwd" in text
    assert "flash_attention_short_bwd" in text
    # the fused projections reach the kernels unsplit and their gradients
    # leave them packed: nothing of their width is split, sliced, padded
    # or concatenated anywhere in the step
    widths = (3 * cfg.d_model, 2 * cfg.d_model)
    moved = [(e.primitive.name, v.aval.shape) for e in _jaxpr_eqns(
        jaxpr.jaxpr) if e.primitive.name in _COPIES
        for v in list(e.invars) + list(e.outvars)
        if getattr(getattr(v, "aval", None), "shape", ())[-1:]
        and v.aval.shape[-1] in widths]
    assert not moved, moved
    H = cfg.n_head
    scores = [a.shape for a in _jaxpr_avals(jaxpr.jaxpr)
              if len(getattr(a, "shape", ())) >= 3
              and a.shape[-2:] == (T, T) and a.size >= B * H * T * T]
    assert not scores, scores
    # the same walk does find them in the composition's step
    fa.set_mode("off")
    try:
        with lowering_for("tpu"):
            plain = jax.make_jaxpr(
                build_step_fn(main, [avg_cost.name], False, None))(
                    persist, feed, jax.random.PRNGKey(0))
    finally:
        fa.set_mode("auto")
    assert any(len(getattr(a, "shape", ())) == 4
               and a.shape == (B, H, T, T)
               for a in _jaxpr_avals(plain.jaxpr))


def test_bench_attention_tool_refuses_without_a_chip(tmp_path, monkeypatch,
                                                     capsys):
    """tools/bench_attention.py (how the crossover table was measured)
    times nothing off the chip: no interpret-mode fallback, no `ms`
    line, no file, exit code 2."""
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "bench_attention.py")
    spec = importlib.util.spec_from_file_location("bench_attention", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    monkeypatch.chdir(tmp_path)
    assert tool.main(["[[2, 32, 32, 2, 16]]", "short,sdpa,tiled", "1"]) == 2
    # a sixth number (key-value heads), blocks for the tiled kernel and
    # the flags are read and still nothing is timed
    assert tool.main(["[[2, 32, 32, 4, 16, 2]]", "sdpa,tiled:16x32", "1",
                      "causal,nobias"]) == 2
    said = capsys.readouterr()
    assert "ms" not in said.out and "not a TPU" in said.err
    assert not (tmp_path / "chiprun_out").exists()
