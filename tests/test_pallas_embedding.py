"""Pallas fused lookup+pool tests (interpret mode on CPU): forward and
backward numerics vs the lowered jnp gather+segment-sum composition,
dispatch gating, the fused_embedding_seq_pool op, and the bit-identity
of the unique-ids dedup gather the sparse engine builds on."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as pt  # noqa: F401  (registers the op kernels)
from paddle_tpu.ops.pallas import embedding as pe
from paddle_tpu.ops.registry import get_kernel, KernelCtx


def _rand(seed=0, C=64, D=16, R=32, F=5):
    rng = np.random.RandomState(seed)
    tab = jnp.asarray(rng.randn(C, D).astype("float32"))
    inv = jnp.asarray(rng.randint(-1, C, (R, F)).astype("int32"))
    w = jnp.asarray(rng.rand(R, F).astype("float32"))
    return tab, inv, w


@pytest.mark.parametrize("pool", ["sum", "mean"])
@pytest.mark.parametrize("weighted", [False, True])
def test_fwd_matches_jnp_composition(pool, weighted):
    tab, inv, w = _rand()
    wt = w if weighted else None
    y = pe.lookup_pool(tab, inv, wt, pool, None, True)
    ref = pe.lookup_pool_reference(tab, inv, wt, pool)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_bwd_matches_jnp_grads():
    tab, inv, w = _rand(seed=1, C=128, D=8, R=16, F=4)

    def loss_k(t, w_):
        return jnp.sum(pe.lookup_pool(t, inv, w_, "sum", None, True) ** 2)

    def loss_r(t, w_):
        return jnp.sum(pe.lookup_pool_reference(t, inv, w_, "sum") ** 2)

    gk = jax.grad(loss_k, argnums=(0, 1))(tab, w)
    gr = jax.grad(loss_r, argnums=(0, 1))(tab, w)
    for a, b, name in zip(gk, gr, ("dtable", "dweights")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


def test_bwd_mean_pool_unweighted():
    tab, inv, _ = _rand(seed=2)
    gk = jax.grad(lambda t: jnp.sum(
        pe.lookup_pool(t, inv, None, "mean", None, True) ** 2))(tab)
    gr = jax.grad(lambda t: jnp.sum(
        pe.lookup_pool_reference(t, inv, None, "mean") ** 2))(tab)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(gr),
                               rtol=2e-4, atol=2e-4)


def test_mean_excludes_invalid_from_denominator():
    tab = jnp.asarray(np.eye(4, dtype="float32"))
    inv = jnp.asarray(np.array([[0, 1, -1, -1]], dtype="int32"))
    y = pe.lookup_pool(tab, inv, None, "mean", None, True)
    # two valid rows -> mean divides by 2, not F=4
    np.testing.assert_allclose(np.asarray(y)[0],
                               np.array([0.5, 0.5, 0, 0]), atol=1e-6)


def test_dispatch_gated_off_cpu():
    tab, inv, _ = _rand()
    assert pe.try_lookup_pool(tab, inv) is None  # no TPU, no interpret


def test_dispatch_active_in_interpret_mode():
    from paddle_tpu.ops.pallas import flash_attention as fa
    tab, inv, _ = _rand(C=64, D=16, R=32, F=5)
    fa.set_mode("interpret")
    try:
        before = pe.STATS["pallas_calls"]
        y = pe.try_lookup_pool(tab, inv, None, "sum")
        assert y is not None
        assert pe.STATS["pallas_calls"] == before + 1
        ref = pe.lookup_pool_reference(tab, inv, None, "sum")
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
    finally:
        fa.set_mode("auto")


def test_fused_embedding_seq_pool_op():
    """The registered op (ref fused_embedding_seq_pool_op.h) equals
    lookup_table + reduce over the field axis, honors padding_idx, and
    supports the weighted pool."""
    rng = np.random.RandomState(3)
    V, D, B, F = 40, 8, 6, 4
    w = jnp.asarray(rng.randn(V, D).astype("float32"))
    ids = rng.randint(0, V, (B, F, 1)).astype("int64")
    ids[0, 0, 0] = 0          # the padding id
    vals = jnp.asarray(rng.rand(B, F).astype("float32"))
    kern = get_kernel("fused_embedding_seq_pool")
    ctx = KernelCtx()
    out = kern(ctx, {"W": [w], "Ids": [jnp.asarray(ids)]},
               {"pooltype": "sum", "padding_idx": -1})["Out"][0]
    ref = np.take(np.asarray(w), ids.reshape(B, F), axis=0).sum(1)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5,
                               atol=1e-5)
    # padding_idx=0 zeroes that position's contribution
    out_p = kern(ctx, {"W": [w], "Ids": [jnp.asarray(ids)]},
                 {"pooltype": "sum", "padding_idx": 0})["Out"][0]
    mask = (ids.reshape(B, F) != 0)[..., None]
    ref_p = (np.take(np.asarray(w), ids.reshape(B, F), axis=0)
             * mask).sum(1)
    np.testing.assert_allclose(np.asarray(out_p), ref_p, rtol=1e-5,
                               atol=1e-5)
    # weighted sum (first-order CTR term)
    out_w = kern(ctx, {"W": [w], "Ids": [jnp.asarray(ids)],
                       "Weight": [vals]},
                 {"pooltype": "sum", "padding_idx": -1})["Out"][0]
    ref_w = (np.take(np.asarray(w), ids.reshape(B, F), axis=0)
             * np.asarray(vals)[..., None]).sum(1)
    np.testing.assert_allclose(np.asarray(out_w), ref_w, rtol=1e-5,
                               atol=1e-5)


def test_dedup_gather_bit_identical_to_direct_gather():
    """The sparse engine's lowering — unique_static dedup, gather the
    unique rows, expand by inverse index — must be BIT-identical to
    the dense path's direct jnp.take: the rows are exact copies, no
    arithmetic touches them."""
    from paddle_tpu.parallel.sparse import unique_static
    rng = np.random.RandomState(7)
    V, D, M = 64, 16, 48
    w = jnp.asarray(rng.randn(V, D).astype("float32"))
    ids = jnp.asarray(rng.randint(0, V, (M,)).astype("int32"))
    uids, inv, count = unique_static(ids)
    u_rows = jnp.take(w, jnp.clip(uids, 0, V - 1), axis=0)
    via_dedup = jnp.take(u_rows, inv, axis=0)
    direct = jnp.take(w, ids, axis=0)
    assert np.asarray(via_dedup).tobytes() == \
        np.asarray(direct).tobytes()
    assert int(count) == len(np.unique(np.asarray(ids)))
    # and through a loss: identical bytes -> identical reduction ->
    # the dedup path's loss is BIT-identical to the dense path's
    loss_dedup = jnp.mean(jnp.square(via_dedup))
    loss_direct = jnp.mean(jnp.square(direct))
    assert float(loss_dedup) == float(loss_direct)
