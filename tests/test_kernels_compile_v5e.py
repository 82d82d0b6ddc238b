"""The main path's Pallas kernels compiled for a described TPU v5e, at the
benchmark cell's real shape. Nothing runs: the TPU compiler is installed
here and compiles for a chip that is described and not attached, so what
Mosaic or XLA:TPU would refuse on the chip is refused here, at no chip
time (interpret mode cannot see a mis-tiled slice or a VMEM overrun).

The topology is described inside a module-scoped fixture, never at
import: only one process may load libtpu, and every xdist worker imports
every test file. Keep such tests in this one file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops.pallas import flash_attention as fa

# the cell nmt_train_1chip: 128 x 256 tokens, 8 heads of 64, bf16
B, H, D = 128, 8, 64


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the
    # persistent cache: keep them out of it, and the run silent
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _instructions(text):
    """(name, result type, opcode, line) of every HLO instruction."""
    pat = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (\(?[a-z0-9]+\[[^=]*?)"
                     r" ([\w\-]+)\(", re.M)
    return [(m.group(1), m.group(2), m.group(3),
             text[m.start():text.find("\n", m.start())])
            for m in pat.finditer(text)]


def _dims(type_str):
    return [tuple(int(d) for d in m.split(",") if d)
            for m in re.findall(r"\[([\d,]*)\]", type_str)]


@pytest.mark.parametrize("T,S,causal", [
    (256, 256, False), (256, 256, True),         # the cell's attentions
    # the other lengths try_flash hands the kernel on the chip: both
    # multiples of 128 in [256, 512], equal or not
    (512, 512, True), (384, 256, False), (256, 512, False)])
def test_short_attention_compiles_for_v5e_at_the_cells_shape(one_chip, T, S,
                                                             causal):
    """Forward and backward of the short-sequence kernel on [128, T,
    512] x [128, S, 512] bf16 with the key-padding bias [128, 1, 1, S],
    as the model hands them over (a free reshape of its [B, T, H*D]
    arrays)."""
    sds = jax.ShapeDtypeStruct
    x = sds((B, T, H * D), jnp.bfloat16, sharding=one_chip)
    y = sds((B, S, H * D), jnp.bfloat16, sharding=one_chip)
    bias = sds((B, 1, 1, S), jnp.float32, sharding=one_chip)
    q_, k_ = sds((B, T, H, D), x.dtype), sds((B, S, H, D), x.dtype)
    assert fa.picks_short(q_, k_, k_, bias, layout="bthd")

    def step(q, k, v, b, g):
        def attend(q, k, v):
            return fa.flash_attention_bthd(
                q.reshape(B, T, H, D), k.reshape(B, S, H, D),
                v.reshape(B, S, H, D), bias=b, causal=causal).reshape(
                    B, T, H * D)
        out, vjp = jax.vjp(attend, q, k, v)
        return (out,) + vjp(g)

    compiled = jax.jit(step).lower(x, y, y, bias, x).compile()
    text = compiled.as_text()
    ins = _instructions(text)
    calls = [i for i in ins if 'custom_call_target="tpu_custom_call"'
             in i[3]]
    names = sorted(i[0] for i in calls)
    assert len(calls) == 2, names
    assert any("flash_attention_short_fwd" in n for n in names), names
    assert any("flash_attention_short_bwd" in n for n in names), names
    # nothing q/k/v-sized is copied into another layout or transposed
    # around the kernels (a copy-start is XLA prefetching an operand,
    # layout unchanged, into on-chip memory: not a pass the kernel forced)
    big = B * min(T, S) * H * D
    moved = [(i[0], i[1]) for i in ins
             if i[2] in ("copy", "transpose")
             and any(_prod(s) >= big for s in _dims(i[1]))]
    assert not moved, moved
    # and no operand or result of a kernel is 64 lanes wide
    for name, rtype, _, line in calls:
        start = line.index("operand_layout_constraints={")
        operands = line[start:line.index("}}", start)]
        shapes = _dims(rtype) + _dims(operands)
        assert len(shapes) >= 6, (name, shapes)
        narrow = [s for s in shapes if s and s[-1] == D]
        assert not narrow, (name, narrow)
    # the scores never reach HBM: the whole module's temporaries are
    # less than one [B, H, T, S] bf16 tensor
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * B * H * T * S


@pytest.mark.parametrize("packed,causal", [("qkv", False), ("qkv", True),
                                           ("kv", False)])
def test_packed_short_attention_compiles_for_v5e_at_the_cells_shape(
        one_chip, packed, causal):
    """The short kernel over the cell's fused projections as their
    matmuls write them: [128, 256, 3 x 512] (self-attention) or q and a
    [128, 256, 2 x 512] kv (cross), bf16, the key-padding bias. The same
    two kernels, handed the packed arrays in place; the backward's
    result is the packed gradient itself, so the module slices, pads,
    concatenates and updates nothing, and holds no temporary."""
    sds = jax.ShapeDtypeStruct
    T = 256
    x = sds((B, T, (3 if packed == "qkv" else 1) * H * D), jnp.bfloat16,
            sharding=one_chip)
    kv = sds((B, T, 2 * H * D), jnp.bfloat16, sharding=one_chip)
    bias = sds((B, 1, 1, T), jnp.float32, sharding=one_chip)
    g = sds((B, T, H * D), jnp.bfloat16, sharding=one_chip)
    assert fa.picks_packed(x, kv, kv, packed, H, bias)

    def step(x, kv, b, g):
        if packed == "qkv":
            out, vjp = jax.vjp(lambda x: fa.flash_attention_packed(
                x, x, x, packed, H, bias=b, causal=causal), x)
        else:
            out, vjp = jax.vjp(lambda x, kv: fa.flash_attention_packed(
                x, kv, kv, packed, H, bias=b, causal=causal), x, kv)
        return (out,) + vjp(g)

    compiled = jax.jit(step).lower(x, kv, bias, g).compile()
    ins = _instructions(compiled.as_text())
    names = sorted(_kernel_names(compiled))
    assert len(names) == 2, names
    assert "flash_attention_short_fwd" in names[1], names
    assert "flash_attention_short_bwd" in names[0], names
    copies = [(i[0], i[2]) for i in ins if i[2] in (
        "slice", "pad", "concatenate", "dynamic-update-slice", "fusion",
        "copy", "transpose")]
    assert not copies, copies
    assert compiled.memory_analysis().temp_size_in_bytes == 0


def _prod(shape):
    n = 1
    for d in shape:
        n *= d
    return n


def _kernel_names(compiled):
    return [i[0] for i in _instructions(compiled.as_text())
            if 'custom_call_target="tpu_custom_call"' in i[3]]


def test_tiled_attention_with_8_key_value_heads_compiles_at_8192(one_chip):
    """The cell lfm2_train_1chip's attention: [2, 8192, 32 x 64] queries
    over 8 key-value heads, causal, bf16, forward and backward through
    the tiled kernels (the op's `bthd` arrays, transposed for them): the
    forward and the ONE backward kernel, whose dq of a key-value head's
    four query heads (32 MiB of VMEM with the output's buffers) fits."""
    Bq, T, Hq, KV = 2, 8192, 32, 8
    sds = jax.ShapeDtypeStruct
    q = sds((Bq, T, Hq, D), jnp.bfloat16, sharding=one_chip)
    kv = sds((Bq, T, KV, D), jnp.bfloat16, sharding=one_chip)
    assert not fa.picks_short(q, kv, kv, None, layout="bthd")
    assert fa.supports(sds((Bq, Hq, T, D), q.dtype),
                       sds((Bq, KV, T, D), q.dtype),
                       sds((Bq, KV, T, D), q.dtype))

    def step(q, k, v, g):
        def attend(q, k, v):
            return fa.flash_attention(
                q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
                causal=True).swapaxes(1, 2)
        out, vjp = jax.vjp(attend, q, k, v)
        return (out,) + vjp(g)

    compiled = jax.jit(step).lower(q, kv, kv, q).compile()
    names = _kernel_names(compiled)
    for kernel in ("flash_attention_fwd", "flash_attention_bwd"):
        assert any(kernel in n for n in names), names
    assert not any("flash_attention_dq" in n or "flash_attention_dkv" in n
                   for n in names), names
    # dk, dv come out at 8 heads, and no [B, H, T, T] scores reach HBM
    _, dq, dk, dv = jax.eval_shape(step, q, kv, kv, q)
    assert dk.shape == dv.shape == (Bq, T, KV, D) and dq.shape == q.shape
    assert compiled.memory_analysis().temp_size_in_bytes \
        < Bq * Hq * T * T * 2 // 8


def test_grouped_expert_products_compile_at_the_cells_widths(one_chip):
    """`moe_expert_ffn` of one expert layer of lfm2_train_1chip: 16384
    tokens, top-4 of 64, 8 experts of 2048 x 1536 held, bf16, forward and
    backward: the four grouped kernels, the experts' whole matrices as
    blocks in VMEM, and `moe_combine` on the way back to the tokens."""
    from paddle_tpu.ops.pallas import grouped_matmul as gm
    N, Hd, F, E, k = 16384, 2048, 1536, 8, 4
    sds = jax.ShapeDtypeStruct

    def S(shape, dtype):
        return sds(shape, dtype, sharding=one_chip)

    def loss(x, tw, w1, w3, w2, idx):
        out, counts = gm.expert_ffn(x, idx, tw, w1, w3, w2, first_expert=0)
        return jnp.sum(out.astype(jnp.float32)), counts

    compiled = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3, 4), has_aux=True)).lower(
            S((N, Hd), jnp.bfloat16), S((N, k), jnp.float32),
            S((E, Hd, F), jnp.bfloat16), S((E, Hd, F), jnp.bfloat16),
            S((E, F, Hd), jnp.bfloat16), S((N, k), jnp.int32)).compile()
    names = _kernel_names(compiled)
    for kernel in ("moe_gmm_swiglu", "moe_gmm", "moe_swiglu_bwd",
                   "moe_tgmm", "moe_combine"):
        assert any(kernel in n for n in names), names
    # three gathers are traced and XLA keeps two: the backward's gather of
    # x is the forward's, buffer and loop (PERF.md section 6, PR 37)
    assert sum("moe_gather_buffer" in n for n in names) == 2, names
    assert len(re.findall(r" while\(", compiled.as_text())) == 2
    # the buffer is the worst case's (every token's 4 pairs here) and the
    # module keeps a handful of buffers of it, not one a product
    rows = gm.buffer_tiles(N, k, E, gm.DEFAULT_TILE_ROWS) \
        * gm.DEFAULT_TILE_ROWS
    assert rows == N * k + E * gm.DEFAULT_TILE_ROWS
    # 2265923584 at the parent of PR 31, whose gather back to the tokens
    # kept a [N, k, Hd] intermediate (268 MB): 1997129728 without it
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 2265923584 - N * k * Hd * 2 // 2


@pytest.mark.parametrize("N,Hd,E,k", [
    (16384, 2048, 8, 4), (8192, 4096, 8, 8), (8192, 2304, 16, 8)],
    ids=["lfm2", "solar", "mellum2"])
def test_the_gather_writes_no_buffer_of_the_worst_case(one_chip, N, Hd, E,
                                                       k):
    """The way into the sorted buffer alone, at the three decoder cells'
    shapes: the buffer is the output of the Mosaic call `moe_gather_buffer`
    (an empty body: nothing of the worst case's M rows is written), the
    loop over the tiles in use updates it in place, and no `broadcast` of
    [M, Hd] zeros is left (570 MB a gather in solar_train_1chip until
    PR 37)."""
    from paddle_tpu.ops.pallas import grouped_matmul as gm
    tm = gm.DEFAULT_TILE_ROWS
    M = gm.buffer_tiles(N, k, E, tm) * tm
    assert M == {2048: 69632, 4096: 69632, 2304: 73728}[Hd]

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def into(x, idx):
        return gm._gather_rows(x, gm.make_plan(idx, 0, E, tm), tm)

    compiled = jax.jit(into).lower(S((N, Hd), jnp.bfloat16),
                                   S((N, k), jnp.int32)).compile()
    assert any("moe_gather_buffer" in n for n in _kernel_names(compiled))
    out = jax.eval_shape(into, S((N, Hd), jnp.bfloat16), S((N, k), jnp.int32))
    assert out.shape == (M, Hd) and out.dtype == jnp.bfloat16
    whole = [i for i in _instructions(compiled.as_text())
             if (M, Hd) in _dims(i[1])]
    assert whole and not [i[3] for i in whole
                          if i[2] in ("broadcast", "copy", "constant")]
    # the plan's index arrays and a tile's rows: no second buffer
    assert compiled.memory_analysis().temp_size_in_bytes < M * Hd * 2 // 8


@pytest.mark.parametrize("weighted", [True, False],
                         ids=["weighted", "unweighted"])
def test_moe_combine_compiles_at_the_cells_shapes(one_chip, weighted):
    """The way back to the tokens alone, as lfm2_train_1chip calls it: a
    [69632, 2048] bf16 buffer, 16384 tokens, top-4, 8 experts held; with
    the routing weights (the forward's `out`) and without (the
    backward's `dx`)."""
    from paddle_tpu.ops.pallas import grouped_matmul as gm
    N, Hd, E, k, tm = 16384, 2048, 8, 4, gm.DEFAULT_TILE_ROWS
    M = gm.buffer_tiles(N, k, E, tm) * tm
    assert M == 69632

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def back(rows, idx, tw):
        plan = gm.make_plan(idx, 0, E, tm)
        return gm._combine(rows, plan, tw if weighted else None, E, tm,
                           False)

    compiled = jax.jit(back).lower(
        S((M, Hd), jnp.bfloat16), S((N, k), jnp.int32),
        S((N, k), jnp.float32)).compile()
    assert any("moe_combine" in n for n in _kernel_names(compiled))
    out = jax.eval_shape(back, S((M, Hd), jnp.bfloat16),
                         S((N, k), jnp.int32), S((N, k), jnp.float32))
    assert out.shape == (N, Hd) and out.dtype == jnp.bfloat16
    # no [N, k, Hd] intermediate: the plan's index arrays and little else
    assert compiled.memory_analysis().temp_size_in_bytes < N * Hd * 2


def test_tiled_attention_at_head_128_with_one_key_value_head_compiles(
        one_chip):
    """The cell solar_train_1chip's attention: [1, 8192, 8 x 128] queries
    over ONE key-value head, causal, bf16. The dq of the key-value head's
    eight query heads is 64 MiB with the output's buffers, FUSED_BWD_VMEM
    to the byte: the one backward kernel runs, and Mosaic takes it."""
    Bq, T, Hq, KV, Dh = 1, 8192, 8, 1, 128
    sds = jax.ShapeDtypeStruct
    q = sds((Bq, T, Hq, Dh), jnp.bfloat16, sharding=one_chip)
    kv = sds((Bq, T, KV, Dh), jnp.bfloat16, sharding=one_chip)
    assert not fa.picks_short(q, kv, kv, None, layout="bthd")
    assert fa._bwd_resident_bytes(Hq // KV, T, Dh, 2) == fa.FUSED_BWD_VMEM

    def step(q, k, v, g):
        def attend(q, k, v):
            return fa.flash_attention(
                q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
                causal=True).swapaxes(1, 2)
        out, vjp = jax.vjp(attend, q, k, v)
        return (out,) + vjp(g)

    compiled = jax.jit(step).lower(q, kv, kv, q).compile()
    names = _kernel_names(compiled)
    for kernel in ("flash_attention_fwd", "flash_attention_bwd"):
        assert any(kernel in n for n in names), names
    assert not any("flash_attention_dq" in n or "flash_attention_dkv" in n
                   for n in names), names
    _, dq, dk, dv = jax.eval_shape(step, q, kv, kv, q)
    assert dk.shape == dv.shape == (Bq, T, KV, Dh) and dq.shape == q.shape


@pytest.mark.parametrize("blocks", [None, (512, 512)])
def test_windowed_attention_at_the_mellum2_cells_shape_compiles(one_chip,
                                                                 blocks):
    """The cell mellum2_train_1chip's sliding layers: [1, 8192, 32 x 128]
    queries over 4 key-value heads, causal, a window of 1024, bf16,
    through try_flash under a TPU lowering as the op reaches it: the
    tiled kernels under the names a windowed call carries, the one
    backward kernel (the dq of a key-value head's eight query heads is
    FUSED_BWD_VMEM to the byte, as solar's), and a grid that walks the
    band's blocks only. With `window=None` the names are the full
    layer's."""
    from paddle_tpu.ops import registry
    Bq, T, Hq, KV, Dh, W = 1, 8192, 32, 4, 128, 1024
    sds = jax.ShapeDtypeStruct
    q = sds((Bq, T, Hq, Dh), jnp.bfloat16, sharding=one_chip)
    kv = sds((Bq, T, KV, Dh), jnp.bfloat16, sharding=one_chip)
    assert fa._bwd_resident_bytes(Hq // KV, T, Dh, 2) == fa.FUSED_BWD_VMEM

    def step(window):
        def run(q, k, v, g):
            def attend(q, k, v):
                if blocks is None:
                    return fa.try_flash(q, k, v, causal=True, layout="bthd",
                                        window=window)
                return fa.flash_attention(
                    q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
                    causal=True, window=window, block_q=blocks[0],
                    block_k=blocks[1]).swapaxes(1, 2)
            out, vjp = jax.vjp(attend, q, k, v)
            return (out,) + vjp(g)
        return run

    counted = dict(fa.STATS)
    with registry.lowering_for("tpu"):
        compiled = jax.jit(step(W)).lower(q, kv, kv, q).compile()
        plain = jax.jit(step(None)).lower(q, kv, kv, q)
        _, dq, dk, dv = jax.eval_shape(step(W), q, kv, kv, q)
    assert fa.STATS["tiled_window"] == counted["tiled_window"] + 2
    assert fa.STATS["tiled_bwd_fused"] == counted["tiled_bwd_fused"] + 3
    names = _kernel_names(compiled)
    assert len(names) == 2, names
    for kernel in ("flash_attention_win_fwd", "flash_attention_win_bwd"):
        assert any(kernel in n for n in names), names
    text = plain.as_text()
    assert "flash_attention_fwd" in text and "flash_attention_bwd" in text
    assert "flash_attention_win" not in text
    assert dk.shape == dv.shape == (Bq, T, KV, Dh) and dq.shape == q.shape
    # the band's steps: at 1024 x 1024 two k blocks a q block of eight,
    # and two q blocks a k block; at 512 x 512 three
    bq, bk = blocks or fa._choose_blocks(T, T, Dh, Dh, causal=True)
    n = T // bq
    steps = fa._k_steps(n, T // bk, bq, bk, 0, W)
    assert steps == (2 if bq == bk == 1024 else 3), (bq, bk, steps)
    assert steps < T // bk


def test_the_chunked_scan_compiles_at_the_cells_shape(one_chip):
    """`kda_attention` as solar_train_1chip calls it: [1, 8192, 8 x 128]
    bf16 q, k, v, a float32 log-decay, forward and gradient through the
    dispatch entry under a TPU lowering: Mosaic takes `kda_fwd` and
    `kda_bwd`, no XLA `while` walks the chunk states or the solve's rows
    (the sequence is the kernels' last grid axis), and what the module
    keeps beside its operands is the state every chunk starts on
    (8 x 128 x [128, 128] float32 = 67.1e6 B) and little else: 84.7e6 B
    read at PR 35 (the composition's was near a gigabyte)."""
    from paddle_tpu.ops import registry
    from paddle_tpu.ops.pallas import kda
    Bq, T, Hq, Dh = 1, 8192, 8, 128
    sds = jax.ShapeDtypeStruct
    x = sds((Bq, T, Hq, Dh), jnp.bfloat16, sharding=one_chip)
    g = sds((Bq, T, Hq, Dh), jnp.float32, sharding=one_chip)
    beta = sds((Bq, T, Hq), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v, g, beta):
        return jnp.sum(kda.try_kda(q, k, v, g, beta).astype(jnp.float32))

    taken = kda.STATS["pallas_calls"]
    with registry.lowering_for("tpu"):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
            x, x, x, g, beta).compile()
    assert kda.STATS["pallas_calls"] == taken + 1
    names = _kernel_names(compiled)
    assert len(names) == 2, names
    assert any("kda_fwd" in n for n in names), names
    assert any("kda_bwd" in n for n in names), names
    opcodes = {i[2] for i in _instructions(compiled.as_text())}
    assert not opcodes & {"while", "triangular-solve", "dot",
                          "convolution"}, opcodes
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e8


def test_the_selective_scan_compiles_at_the_cells_shape(one_chip):
    """`selective_scan` as jamba2_train_1chip calls it: [1, 8192, 1280]
    bf16 x, a float32 step, A_log [1280, 16], bf16 B and C [1, 8192, 16],
    forward and gradient through the dispatch entry under a TPU lowering:
    Mosaic takes `selective_scan_fwd` and `selective_scan_bwd`, no XLA
    `while` walks the tokens (the sequence is the kernels' last grid axis),
    and the module keeps the state every chunk starts on (32 x [16, 1280]
    float32 = 2.6e6 B) and nothing of [T, C, N]: a layer's whole state
    history would be 671e6 B."""
    from paddle_tpu.ops import registry
    from paddle_tpu.ops.pallas import selective_scan as ssm
    T, C, N = 8192, 1280, 16
    sds = jax.ShapeDtypeStruct
    args = (sds((1, T, C), jnp.bfloat16, sharding=one_chip),
            sds((1, T, C), jnp.float32, sharding=one_chip),
            sds((C, N), jnp.float32, sharding=one_chip),
            sds((1, T, N), jnp.bfloat16, sharding=one_chip),
            sds((1, T, N), jnp.bfloat16, sharding=one_chip),
            sds((C,), jnp.float32, sharding=one_chip))

    def loss(*a):
        return jnp.sum(ssm.try_selective_scan(*a).astype(jnp.float32))

    taken = ssm.STATS["pallas_calls"]
    with registry.lowering_for("tpu"):
        compiled = jax.jit(jax.grad(loss, argnums=tuple(range(6)))).lower(
            *args).compile()
    assert ssm.STATS["pallas_calls"] == taken + 1
    # (the backward's result tuple has six members and an /*index=5*/
    # comment in its type, which _instructions' pattern does not cross)
    names = [line.split(" = ")[0].strip().lstrip("%")
             for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(names) == 2, names
    assert any("selective_scan_fwd" in n for n in names), names
    assert any("selective_scan_bwd" in n for n in names), names
    opcodes = {i[2] for i in _instructions(compiled.as_text())}
    assert "while" not in opcodes, opcodes
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e8
