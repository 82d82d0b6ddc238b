"""Parallel tests on the 8-virtual-device CPU mesh (SURVEY §4):
dp == single-device numerics, ring attention == full attention,
collectives basics, ZeRO sharding plan."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.parallel.mesh import make_mesh, local_mesh
from paddle_tpu.parallel.ring_attention import ring_attention


def _build_mlp():
    img = layers.data("img", shape=[32])
    label = layers.data("label", shape=[1], dtype="int64")
    h = layers.fc(img, size=64, act="relu")
    pred = layers.fc(h, size=10, act="softmax")
    loss = layers.mean(layers.cross_entropy(pred, label))
    pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return loss


def test_devices_available():
    assert len(jax.devices()) == 8


def test_parallel_executor_reports_mesh_and_refuses_missing_tpu():
    """ParallelExecutor takes the process's devices like Executor()
    does and says which (platform, device_count); an explicit
    use_tpu=True over a CPU mesh raises instead of running there."""
    loss = _build_mlp()
    pexe = pt.ParallelExecutor(loss_name=loss.name)   # use_cuda=True
    assert (pexe.platform, pexe.device_count) == ("cpu", 8)
    with pytest.raises(RuntimeError, match="8 'cpu' device"):
        pt.ParallelExecutor(loss_name=loss.name, use_tpu=True)


def test_pallas_dispatch_follows_the_lowering_target():
    """Mosaic kernels lower only where the program is compiled for a
    TPU, unpartitioned: the tracers scope that from their Place / mesh
    (ops.registry.lowering_for), not from the process default."""
    from paddle_tpu.ops.registry import lowering_for, mosaic_target
    assert mosaic_target() is False          # default backend: cpu
    with lowering_for("tpu"):
        assert mosaic_target() is True
        with lowering_for("cpu"):            # Executor(CPUPlace())
            assert mosaic_target() is False
        with lowering_for(None):             # place=None: unchanged
            assert mosaic_target() is True
        with lowering_for("tpu", partitioned=True):   # GSPMD jit
            assert mosaic_target() is False
        assert mosaic_target() is True
    assert mosaic_target() is False


def test_ring_attention_matches_full():
    mesh = make_mesh(sp=8)
    B, H, T, D = 2, 4, 64, 16
    rng = np.random.RandomState(0)
    q, k, v = [jnp.asarray(rng.randn(B, H, T, D).astype("float32"))
               for _ in range(3)]
    for causal in (False, True):
        out = ring_attention(mesh, q, k, v, causal=causal)
        s = jnp.einsum("bhqd,bhkd->bhqk", q * D ** -0.5, k)
        if causal:
            cm = jnp.tril(jnp.ones((T, T), bool))
            s = jnp.where(cm, s, -jnp.inf)
        ref = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)


def test_parallel_executor_matches_single_device():
    rng = np.random.RandomState(0)
    imgs = rng.randn(16, 32).astype("float32")
    lbls = rng.randint(0, 10, size=(16, 1)).astype("int64")

    # single-device run
    prog_a = pt.Program()
    startup_a = pt.Program()
    with pt.program_guard(prog_a, startup_a):
        with pt.unique_name.guard():
            loss_a = _build_mlp()
    prog_a.random_seed = 7
    startup_a.random_seed = 7
    exe = pt.Executor(pt.CPUPlace())
    scope_a = pt.Scope()
    with pt.scope_guard(scope_a):
        exe.run(startup_a)
        single = [float(exe.run(prog_a, feed={"img": imgs, "label": lbls},
                                fetch_list=[loss_a])[0]) for _ in range(3)]

    # data-parallel run over 8 devices, same seed → same numerics
    prog_b = pt.Program()
    startup_b = pt.Program()
    with pt.program_guard(prog_b, startup_b):
        with pt.unique_name.guard():
            loss_b = _build_mlp()
    prog_b.random_seed = 7
    startup_b.random_seed = 7
    scope_b = pt.Scope()
    with pt.scope_guard(scope_b):
        exe2 = pt.Executor(pt.CPUPlace())
        exe2.run(startup_b)
        pexe = pt.ParallelExecutor(loss_name=loss_b.name,
                                   main_program=prog_b)
        par = [float(pexe.run(feed={"img": imgs, "label": lbls},
                              fetch_list=[loss_b])[0]) for _ in range(3)]

    np.testing.assert_allclose(single, par, rtol=1e-5)


def test_collectives_shard_map():
    from paddle_tpu.parallel import collective as C
    mesh = local_mesh("dp")
    x = jnp.arange(8.0)

    f = jax.shard_map(lambda v: C.all_reduce(v, "sum", "dp"),
                      mesh=mesh, in_specs=jax.sharding.PartitionSpec("dp"),
                      out_specs=jax.sharding.PartitionSpec("dp"))
    out = f(x)
    np.testing.assert_allclose(np.asarray(out), np.full(8, x.sum()))

    g = jax.shard_map(lambda v: C.all_gather(v, "dp", axis=0),
                      mesh=mesh, in_specs=jax.sharding.PartitionSpec("dp"),
                      out_specs=jax.sharding.PartitionSpec(None),
                      check_vma=False)
    np.testing.assert_allclose(np.asarray(g(x))[:8], np.arange(8.0))


def test_all_reduce_prod_handles_zero_and_negative():
    """Regression (ISSUE 6 satellite): exp(psum(log x)) NaN'd on
    negative members and poisoned the result with -inf-driven garbage
    on zeros; the sign/zero-mask/log-magnitude decomposition must
    return the true product."""
    from paddle_tpu.parallel import collective as C
    mesh = local_mesh("dp")
    f = jax.shard_map(lambda v: C.all_reduce(v, "prod", "dp"),
                      mesh=mesh, in_specs=jax.sharding.PartitionSpec("dp"),
                      out_specs=jax.sharding.PartitionSpec("dp"),
                      check_vma=False)
    cases = [
        [2.0, -3.0, 0.0, 1.5, -1.0, 4.0, -2.0, 0.5],   # zero + negatives
        [2.0, -3.0, 5.0, 1.5, -1.0, 4.0, -2.0, 0.5],   # odd negatives
        [2.0, 3.0, 5.0, 1.5, 1.0, 4.0, 2.0, 0.5],      # all positive
        [-1.0] * 8,                                     # even negatives
        [0.0] * 8,
    ]
    for vals in cases:
        x = jnp.asarray(vals, jnp.float32)
        out = np.asarray(f(x))
        expect = float(np.prod(np.asarray(vals, np.float64)))
        np.testing.assert_allclose(out, np.full(8, expect),
                                   rtol=1e-5, atol=1e-6)
        assert np.isfinite(out).all()
    # elementwise vectors reduce per element too
    xv = jnp.asarray(np.arange(16, dtype="float32").reshape(8, 2) - 7.0)
    out = np.asarray(f(xv))
    expect = np.prod(np.asarray(xv, np.float64), axis=0)
    np.testing.assert_allclose(out[0], expect, rtol=1e-5, atol=1e-6)


def test_pmin_raw_alias_exported():
    """pmin was reachable only via all_reduce(op="min"); the raw alias
    must exist alongside psum/pmean/pmax and be exported."""
    from paddle_tpu.parallel import collective as C
    assert "pmin" in C.__all__
    mesh = local_mesh("dp")
    x = jnp.asarray([3.0, -2.0, 7.0, 0.5, 9.0, -8.0, 1.0, 4.0])
    f = jax.shard_map(lambda v: C.pmin(v, "dp"), mesh=mesh,
                      in_specs=jax.sharding.PartitionSpec("dp"),
                      out_specs=jax.sharding.PartitionSpec("dp"),
                      check_vma=False)
    np.testing.assert_allclose(np.asarray(f(x)), np.full(8, -8.0))
    # gradsync rides the same module; its export is part of the wiring
    import paddle_tpu.parallel as par
    assert hasattr(par, "gradsync")
    assert par.GradSyncPolicy is par.gradsync.GradSyncPolicy


def test_transpiler_builds_plan():
    prog = pt.Program()
    startup = pt.Program()
    with pt.program_guard(prog, startup):
        loss = _build_mlp()
    cfg = pt.parallel.DistributeTranspilerConfig()
    cfg.mode = "zero"
    t = pt.parallel.DistributeTranspiler(cfg)
    t.transpile(program=prog)
    sh = t.shardings()
    assert len(sh) > 0
    # optimizer state missing here (SGD), but params replicated
    assert all(s.mesh is t.mesh for s in sh.values())


def test_ulysses_attention_matches_full():
    """All-to-all sequence parallelism == dense attention (the Ulysses
    complement to ring attention; SURVEY §2.4)."""
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.parallel.ulysses import ulysses_attention
    import jax.numpy as jnp
    mesh = make_mesh(sp=8)
    rng = np.random.RandomState(0)
    B, H, T, D = 2, 8, 32, 16
    q = jnp.asarray(rng.randn(B, H, T, D).astype("float32"))
    k = jnp.asarray(rng.randn(B, H, T, D).astype("float32"))
    v = jnp.asarray(rng.randn(B, H, T, D).astype("float32"))
    for causal in (False, True):
        out = ulysses_attention(mesh, q, k, v, causal=causal)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (D ** -0.5)
        if causal:
            s = jnp.where(jnp.tril(jnp.ones((T, T), dtype=bool)), s, -1e30)
        ref = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


def test_ring_attention_flash_blocks_match_full():
    """Ring attention with the Pallas flash kernel as the per-block
    engine (interpret mode): forward AND gradients match full attention
    — the lse-returning custom_vjp merges correctly across ring hops."""
    from paddle_tpu.ops.pallas import flash_attention as fa
    mesh = make_mesh(sp=4)
    B, H, T, D = 1, 2, 64, 16
    rng = np.random.RandomState(3)
    q, k, v = [jnp.asarray(rng.randn(B, H, T, D).astype("float32"))
               for _ in range(3)]
    fa.set_mode("interpret")
    calls_before = fa.STATS["pallas_calls"]
    try:
        for causal in (False, True):
            def ring_loss(q, k, v):
                o = ring_attention(mesh, q, k, v, causal=causal)
                return jnp.sum(o.astype(jnp.float32) ** 2)

            def full_loss(q, k, v):
                s = jnp.einsum("bhqd,bhkd->bhqk", q * D ** -0.5, k)
                if causal:
                    cm = jnp.tril(jnp.ones((T, T), bool))
                    s = jnp.where(cm, s, -1e30)
                o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
                return jnp.sum(o ** 2)

            out = ring_attention(mesh, q, k, v, causal=causal)
            s = jnp.einsum("bhqd,bhkd->bhqk", q * D ** -0.5, k)
            if causal:
                cm = jnp.tril(jnp.ones((T, T), bool))
                s = jnp.where(cm, s, -1e30)
            ref = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=3e-5)
            g1 = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
            g2 = jax.grad(full_loss, argnums=(0, 1, 2))(q, k, v)
            for a, b in zip(g1, g2):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-4, atol=1e-4)
        # the kernel (not the jnp fallback) must actually have run
        assert fa.STATS["pallas_calls"] > calls_before
    finally:
        fa.set_mode("auto")


def test_ulysses_flash_local_matches_full():
    """Ulysses with the Pallas kernel as the local engine (interpret
    mode) matches full attention."""
    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.parallel.ulysses import ulysses_attention
    mesh = make_mesh(sp=4)
    B, H, T, D = 1, 4, 32, 16
    rng = np.random.RandomState(5)
    q, k, v = [jnp.asarray(rng.randn(B, H, T, D).astype("float32"))
               for _ in range(3)]
    fa.set_mode("interpret")
    calls_before = fa.STATS["pallas_calls"]
    try:
        out = ulysses_attention(mesh, q, k, v, causal=True)
        s = jnp.einsum("bhqd,bhkd->bhqk", q * D ** -0.5, k)
        cm = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(cm, s, -1e30)
        ref = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5)
        assert fa.STATS["pallas_calls"] > calls_before
    finally:
        fa.set_mode("auto")
