"""Worker process for tests/test_multihost.py — NOT a test module.

Run as: python _multihost_worker.py <pid> <nproc> <port> [mode] [dir]

Initializes the real multi-process runtime (fleet.init →
jax.distributed.initialize) on the CPU backend with 2 local virtual
devices per process, builds a GLOBAL mesh spanning both processes, and
runs the selected check:

- mode "psum" (default): a psum whose operand is globally sharded —
  the XLA collective actually crosses the process boundary (the
  reference's NCCL/gRPC all-reduce analog,
  paddle/fluid/operators/distributed/grpc_server.cc).
- mode "ckpt": each host saves only ITS shards of a global array via
  save_sharded_checkpoint into <dir> (barrier before AND after the
  host-0 publish rename), then loads it back and checks its local
  shards — the pserver checkpoint RPC analog.
- mode "train": FULL data-parallel training through ParallelExecutor
  (each host feeds its local batch) == single-process global-batch
  numerics.
- mode "tp": dp x tp over the multi-host mesh (Megatron-sharded
  weights, tp intra-host, dp across hosts) == single-process
  numerics.
- mode "sp": causal ring attention with the sp axis spanning both
  processes; fwd + q/k/v grads == dense reference.
- mode "pp": GPipe AND 1F1B pipeline training with the pp axis
  spanning both processes; == single-device dense run.

Prints "RESULT ..." on success.
"""
import os
import sys


def main():
    pid, nproc, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
    mode = sys.argv[4] if len(sys.argv) > 4 else "psum"
    workdir = sys.argv[5] if len(sys.argv) > 5 else None
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=2")

    import numpy as np
    import jax

    # pure CPU like a DCN host (same belt and braces as
    # tests/conftest.py: env before import, config after)
    jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.parallel import fleet

    print(f"[w{pid}] imported jax, env JAX_PLATFORMS="
          f"{os.environ.get('JAX_PLATFORMS')} XLA_FLAGS="
          f"{os.environ.get('XLA_FLAGS')}", flush=True)
    fleet.init(coordinator_address=f"localhost:{port}",
               num_processes=nproc, process_id=pid)
    print(f"[w{pid}] fleet.init done", flush=True)
    # fleet observability: init tagged this process's telemetry with
    # its rank; flush the rank snapshot spool on exit so a run with
    # PADDLE_TPU_TELEMETRY=1 (+ PADDLE_TPU_FLEET_DIR) is mergeable via
    # `tpustat --fleet`. No-op when telemetry is off.
    import atexit
    from paddle_tpu import telemetry
    atexit.register(lambda: telemetry.flush(log=False))
    assert fleet.worker_num() == nproc, fleet.worker_num()
    assert fleet.worker_index() == pid
    n_global = len(jax.devices())
    print(f"[w{pid}] devices: {jax.devices()}", flush=True)
    assert n_global == 2 * nproc, jax.devices()
    assert len(jax.local_devices()) == 2

    # cross-process barrier (sync_global_devices path)
    fleet.barrier_all()
    print(f"[w{pid}] barrier done", flush=True)

    # global mesh over all processes' devices
    mesh = Mesh(np.array(jax.devices()), ("dp",))
    sharding = NamedSharding(mesh, P("dp"))

    if mode == "ckpt":
        from paddle_tpu.io import (save_sharded_checkpoint,
                                   load_sharded_checkpoint)
        rows = np.arange(n_global * 8, dtype=np.float32).reshape(
            n_global, 8)
        garr = jax.make_array_from_callback(
            rows.shape, NamedSharding(mesh, P("dp", None)),
            lambda idx: rows[idx])
        save_sharded_checkpoint(workdir, {"w": garr}, step=3)
        # the post-publish barrier inside save guarantees the rename
        # has landed for EVERY host before any host loads
        restored, meta = load_sharded_checkpoint(workdir, mesh=mesh)
        assert meta["step"] == 3, meta
        w2 = restored["w"]
        for shard in w2.addressable_shards:
            np.testing.assert_array_equal(
                np.asarray(shard.data), rows[shard.index])
        print(f"RESULT ckpt-ok {fleet.worker_num()} {n_global}",
              flush=True)
        return

    if mode == "train":
        _train_mode(pid, nproc, mesh, n_global)
        return
    if mode == "tp":
        _tp_mode(pid, nproc, n_global)
        return
    if mode == "sp":
        _sp_mode(pid, nproc, n_global)
        return
    if mode == "pp":
        _pp_mode(pid, nproc, n_global)
        return
    if mode == "table":
        _table_mode(pid, nproc, n_global)
        return
    if mode == "ep":
        _ep_mode(pid, nproc, n_global)
        return

    # operand sharded over the global mesh, device d contributing (d+1)
    contrib = np.arange(1, n_global + 1, dtype=np.float32)
    garr = jax.make_array_from_callback(
        (n_global,), sharding, lambda idx: contrib[idx])

    f = jax.jit(jax.shard_map(lambda x: jax.lax.psum(x, "dp"),
                              mesh=mesh, in_specs=P("dp"),
                              out_specs=P()))
    total = float(np.asarray(f(garr))[0])
    expected = float(contrib.sum())
    assert total == expected, (total, expected)
    print(f"RESULT {total} {fleet.worker_num()} {n_global}", flush=True)


def _table_mode(pid, nproc, n_global):
    """Cross-host DISTRIBUTED LOOKUP TABLE: embedding(
    is_distributed=True) row-shards the table AND its Adam moments over
    the GLOBAL dp axis (vocab/n_global rows per device, spanning both
    OS processes); XLA SPMD partitions the gather and the sparse
    scatter-update so row fetches cross the host boundary — the
    pserver prefetch/push RPC analog
    (ref operators/distributed/grpc_server.cc + downpour). Each host
    feeds its LOCAL batch; losses must equal a single-process
    replicated run on the global batch."""
    import numpy as np
    import jax
    from jax.sharding import PartitionSpec as P
    import paddle_tpu as pt
    from paddle_tpu import layers

    vocab, dim = 64, 8
    rng = np.random.RandomState(33)    # same on both hosts
    B_local, steps = 4, 3
    ids1 = rng.randint(0, vocab, (1, nproc, B_local, 4, 1)).astype(
        "int64")
    ys1 = rng.randn(1, nproc, B_local, dim).astype("float32")
    ids = np.repeat(ids1, steps, 0)
    ys = np.repeat(ys1, steps, 0)

    def build():
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            with pt.unique_name.guard():
                i = layers.data("ids", shape=[4, 1], dtype="int64")
                y = layers.data("y", shape=[dim], dtype="float32")
                emb = layers.embedding(
                    i, size=[vocab, dim], is_sparse=True,
                    is_distributed=True,
                    param_attr=pt.ParamAttr(name="big_table"))
                loss = layers.mean(layers.square_error_cost(
                    layers.reduce_sum(emb, dim=1), y))
                pt.optimizer.Adam(1e-2).minimize(loss)
        main.random_seed = startup.random_seed = 17
        return main, startup, loss

    main_b, startup_b, loss_b = build()
    t = pt.parallel.DistributeTranspiler(
        pt.parallel.DistributeTranspilerConfig())
    t.transpile(program=main_b)
    sh = t.shardings()
    assert sh["big_table"].spec == P("dp", None), sh["big_table"]
    scope_b = pt.Scope()
    with pt.scope_guard(scope_b):
        exe2 = pt.Executor(pt.CPUPlace())
        exe2.run(startup_b)
        pexe = pt.ParallelExecutor(loss_name=loss_b.name,
                                   main_program=main_b, transpiler=t,
                                   scope=scope_b)
        par = []
        for s in range(steps):
            out = pexe.run(feed={"ids": ids[s, pid], "y": ys[s, pid]},
                           fetch_list=[loss_b])
            par.append(float(np.asarray(out[0])))
        # the table is genuinely row-sharded: this host's shards hold
        # vocab/n_global rows each, not the full table
        table = scope_b.get("big_table")
        for shard in table.addressable_shards:
            assert shard.data.shape[0] == vocab // n_global,                 shard.data.shape

    # single-process replicated reference on the global batch
    main_a, startup_a, loss_a = build()
    scope_a = pt.Scope()
    with pt.scope_guard(scope_a):
        exe = pt.Executor(pt.CPUPlace())
        exe.run(startup_a)
        base = []
        for s in range(steps):
            g_ids = ids[s].reshape(nproc * B_local, 4, 1)
            g_y = ys[s].reshape(nproc * B_local, dim)
            base.append(float(np.asarray(exe.run(
                main_a, feed={"ids": g_ids, "y": g_y},
                fetch_list=[loss_a])[0])))

    np.testing.assert_allclose(par, base, rtol=1e-4, atol=1e-6)
    assert par[-1] < par[0], par

    # tpusparse ENGINE leg (parallel/sparse.py): the same table driven
    # by the explicit mod-sharded engine — unique-ids dedup + the
    # all-to-all row exchange CROSS the host boundary (the pserver
    # prefetch/push RPC, now explicit collectives). Each host feeds its
    # LOCAL batch; losses must equal the replicated global-batch run.
    main_c, startup_c, loss_c = build()
    scope_c = pt.Scope()
    with pt.scope_guard(scope_c):
        exe3 = pt.Executor(pt.CPUPlace())
        exe3.run(startup_c)
        pexe2 = pt.ParallelExecutor(loss_name=loss_c.name,
                                    main_program=main_c, scope=scope_c,
                                    sparse="shard")
        eng = []
        for s in range(steps):
            out = pexe2.run(feed={"ids": ids[s, pid], "y": ys[s, pid]},
                            fetch_list=[loss_c])
            eng.append(float(np.asarray(out[0])))
        table = scope_c.get("big_table")
        for shard in table.addressable_shards:
            assert shard.data.shape[0] == vocab // n_global, \
                shard.data.shape
    np.testing.assert_allclose(eng, base, rtol=1e-4, atol=1e-6)
    print(f"RESULT table-ok {nproc} {n_global} "
          f"{' '.join(f'{l:.6f}' for l in par)}", flush=True)


def _ep_mode(pid, nproc, n_global):
    """Cross-host EXPERT PARALLELISM: switch-MoE FFN with one expert
    per device over a global ep axis spanning both OS processes — the
    dispatch/combine all-to-alls cross the host boundary. Loss and
    grads must be finite, equal on both hosts (replicated outputs),
    and equal to a single-mesh computation of the same shapes run on
    this host's 2 local devices with the same params/tokens (the MoE
    math is deterministic in expert count, not device layout)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.parallel.moe import init_moe_params, moe_ffn

    D, H = 8, 16
    E = n_global                     # one expert per global device
    N = 8 * n_global                 # tokens
    params = init_moe_params(jax.random.PRNGKey(0), D, H, E)
    x = jax.random.normal(jax.random.PRNGKey(1), (N, D))

    def loss_fn(x, p, mesh):
        out, aux = moe_ffn(x, p, mesh=mesh)
        return jnp.sum(out ** 2) + 0.01 * aux

    gmesh = make_mesh(ep=n_global, devices=jax.devices())
    val, grads = jax.jit(
        jax.value_and_grad(lambda x, p: loss_fn(x, p, gmesh),
                           argnums=(0, 1)))(x, params)
    jax.block_until_ready(grads)
    val = float(np.asarray(val))
    assert np.isfinite(val), val
    for g in jax.tree_util.tree_leaves(grads):
        # grads span non-addressable devices: inspect LOCAL shards
        for shard in g.addressable_shards:
            assert np.isfinite(np.asarray(shard.data)).all()

    # reference: same experts/tokens on a LOCAL 2-device mesh — the
    # routing and math depend on E, not on how experts are placed
    lmesh = make_mesh(ep=2, devices=jax.local_devices())
    ref = float(np.asarray(jax.jit(
        lambda x, p: loss_fn(x, p, lmesh))(x, params)))
    np.testing.assert_allclose(val, ref, rtol=1e-5)
    print(f"RESULT ep-ok {nproc} {n_global} {val:.6f}", flush=True)


def _build_mlp_program(seed, in_dim=6, hidden=8, out_dim=4,
                       tp_names=False):
    """Shared MLP builder; tp_names=True gives the fc params the
    fc1_col/fc2_row names the Megatron tp rules match."""
    import paddle_tpu as pt
    from paddle_tpu import layers
    main, startup = pt.Program(), pt.Program()
    main.random_seed = startup.random_seed = seed
    with pt.program_guard(main, startup):
        with pt.unique_name.guard():
            x = layers.data("x", shape=[in_dim])
            y = layers.data("y", shape=[out_dim])
            a1 = pt.ParamAttr(name="fc1_col.w") if tp_names else None
            a2 = pt.ParamAttr(name="fc2_row.w") if tp_names else None
            h = layers.fc(x, size=hidden, act="relu", param_attr=a1)
            pred = layers.fc(h, size=out_dim, param_attr=a2)
            loss = layers.mean(layers.square_error_cost(pred, y))
            pt.optimizer.SGD(0.1).minimize(loss)
    return main, startup, loss


def _train_mode(pid, nproc, mesh, n_global):
    """Multi-host DATA-PARALLEL TRAINING through ParallelExecutor:
    each host feeds its LOCAL batch; the losses must match a
    single-process run on the concatenated global batch (computed
    locally for comparison — same seeds, same init)."""
    import numpy as np
    import jax
    import paddle_tpu as pt

    rng = np.random.RandomState(42)   # same on both hosts
    B_local, steps = 4, 3
    # one fixed batch repeated: parity AND monotone loss decrease
    x1 = rng.randn(1, nproc, B_local, 6).astype("float32")
    y1 = rng.randn(1, nproc, B_local, 4).astype("float32")
    xs = np.repeat(x1, steps, axis=0)
    ys = np.repeat(y1, steps, axis=0)

    main, startup, loss = _build_mlp_program(seed=9)
    scope = pt.Scope()
    exe = pt.Executor(pt.CPUPlace())
    with pt.scope_guard(scope):
        exe.run(startup)
        pexe = pt.ParallelExecutor(loss_name=loss.name,
                                   main_program=main, mesh=mesh,
                                   scope=scope)
        losses = []
        for s in range(steps):
            out = pexe.run(feed={"x": xs[s, pid], "y": ys[s, pid]},
                           fetch_list=[loss])
            losses.append(float(np.asarray(out[0])))

    # reference: single-process global-batch simulation (pure host
    # math through the same program machinery on unsharded arrays)
    main2, startup2, loss2 = _build_mlp_program(seed=9)
    scope2 = pt.Scope()
    with pt.scope_guard(scope2):
        exe2 = pt.Executor(pt.CPUPlace())
        exe2.run(startup2)
        expect = []
        for s in range(steps):
            gx = xs[s].reshape(nproc * B_local, 6)
            gy = ys[s].reshape(nproc * B_local, 4)
            out = exe2.run(main2, feed={"x": gx, "y": gy},
                           fetch_list=[loss2])
            expect.append(float(np.asarray(out[0])))

    np.testing.assert_allclose(losses, expect, rtol=1e-5, atol=1e-6)
    assert losses[-1] < losses[0]
    print(f"RESULT train-ok {nproc} {n_global} "
          f"{' '.join(f'{l:.6f}' for l in losses)}", flush=True)


def _tp_mode(pid, nproc, n_global):
    """dp x tp over a multi-host mesh in the canonical layout (tp on
    the fast intra-host axis, dp across hosts — the scaling-book
    arrangement of ICI vs DCN): the transpiler's Megatron rules shard
    fc weights over tp, each host materializes only its addressable
    weight shards, dp grads all-reduce across the host boundary; the
    losses must equal the single-process run."""
    import numpy as np
    import paddle_tpu as pt

    rng = np.random.RandomState(7)
    B_local, steps = 4, 3
    x1 = rng.randn(1, nproc, B_local, 8).astype("float32")
    y1 = rng.randn(1, nproc, B_local, 4).astype("float32")
    xs, ys = np.repeat(x1, steps, 0), np.repeat(y1, steps, 0)

    def build():
        return _build_mlp_program(seed=13, in_dim=8, hidden=16,
                                  out_dim=4, tp_names=True)

    from jax.sharding import PartitionSpec as P
    main, startup, loss = build()
    cfg = pt.parallel.DistributeTranspilerConfig()
    cfg.tp = 2                       # tp intra-host, dp across hosts
    t = pt.parallel.DistributeTranspiler(cfg)
    t.transpile(program=main)
    # the test is only meaningful if the weights ARE tp-sharded
    assert t.shardings()["fc1_col.w"].spec == P(None, "tp"), \
        t.shardings()["fc1_col.w"]
    assert t.shardings()["fc2_row.w"].spec == P("tp", None), \
        t.shardings()["fc2_row.w"]
    scope = pt.Scope()
    exe = pt.Executor(pt.CPUPlace())
    with pt.scope_guard(scope):
        exe.run(startup)
        pexe = pt.ParallelExecutor(loss_name=loss.name,
                                   main_program=main, transpiler=t,
                                   scope=scope)
        losses = [float(np.asarray(pexe.run(
            feed={"x": xs[s, pid], "y": ys[s, pid]},
            fetch_list=[loss])[0])) for s in range(steps)]

    main2, startup2, loss2 = build()
    scope2 = pt.Scope()
    with pt.scope_guard(scope2):
        exe2 = pt.Executor(pt.CPUPlace())
        exe2.run(startup2)
        expect = [float(np.asarray(exe2.run(
            main2, feed={"x": xs[s].reshape(-1, 8),
                         "y": ys[s].reshape(-1, 4)},
            fetch_list=[loss2])[0])) for s in range(steps)]

    np.testing.assert_allclose(losses, expect, rtol=1e-5, atol=1e-6)
    print(f"RESULT tp-ok {nproc} {n_global}", flush=True)


def _sp_mode(pid, nproc, n_global):
    """SEQUENCE parallelism across the host boundary: causal ring
    attention over an sp axis spanning both processes — every K/V hop
    is a ppermute whose neighbor link crosses hosts (the long-context
    story on DCN, not just the virtual single-process mesh). Forward
    AND grads must equal the local dense reference."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from paddle_tpu.parallel.ring_attention import ring_attention

    mesh = Mesh(np.array(jax.devices()), ("sp",))
    B, H, D = 1, 2, 4
    T = 8 * n_global
    rng = np.random.RandomState(3)
    qn, kn, vn = (rng.randn(B, H, T, D).astype("float32")
                  for _ in range(3))
    sh = NamedSharding(mesh, P(None, None, "sp", None))
    qg, kg, vg = (jax.make_array_from_callback(
        a.shape, sh, lambda idx, a=a: a[idx]) for a in (qn, kn, vn))

    def ring_loss(q, k, v):
        return ring_attention(mesh, q, k, v, causal=True).sum()

    val, grads = jax.jit(jax.value_and_grad(ring_loss,
                                            argnums=(0, 1, 2)))(qg, kg, vg)

    # dense reference on ONE local device (no mesh, no collectives)
    def dense_loss(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (D ** -0.5)
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v).sum()

    eval_, egrads = jax.value_and_grad(dense_loss,
                                       argnums=(0, 1, 2))(qn, kn, vn)
    np.testing.assert_allclose(float(val), float(eval_),
                               rtol=2e-4, atol=2e-4)
    for g, eg in zip(grads, egrads):
        eg = np.asarray(eg)
        for shard in g.addressable_shards:
            np.testing.assert_allclose(np.asarray(shard.data),
                                       eg[shard.index],
                                       rtol=2e-4, atol=2e-4)
    print(f"RESULT sp-ok {nproc} {n_global}", flush=True)


def _pp_mode(pid, nproc, n_global):
    """PIPELINE parallelism across the host boundary: a 4-stage MLP on
    a pp=4 mesh spanning both processes — the stage-2→stage-3 activation
    ppermute crosses hosts every microbatch (the DCN pipeline story).
    GPipe losses must equal the single-device dense run; the 1F1B
    schedule must match GPipe bit-for-bit."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu import layers
    from paddle_tpu.parallel.mesh import make_mesh
    from paddle_tpu.parallel.pipeline import PipelineTrainer

    D = 8

    def build():
        main, startup = pt.Program(), pt.Program()
        main.random_seed = startup.random_seed = 5
        bnames = []
        with pt.program_guard(main, startup):
            with pt.unique_name.guard():
                x = layers.data("x", shape=[D])
                label = layers.data("label", shape=[D])
                h = x
                for i in range(4):
                    h = layers.fc(
                        h, size=D, act="relu" if i < 3 else None,
                        param_attr=pt.ParamAttr(name=f"mh_fc{i}.w"),
                        bias_attr=pt.ParamAttr(name=f"mh_fc{i}.b"))
                    if i < 3:
                        bnames.append(h.name)
                loss = layers.mean(layers.square_error_cost(h, label))
                pt.optimizer.SGD(0.05).minimize(loss)
        return main, startup, loss, bnames

    main, startup, loss, bnames = build()
    exe = pt.Executor(pt.CPUPlace())
    scope0 = pt.Scope()
    with pt.scope_guard(scope0):
        exe.run(startup)
    snapshot = {v.name: np.asarray(scope0.get(v.name))
                for v in main.persistable_vars()}

    rng = np.random.RandomState(3)
    # one fixed batch repeated: parity AND monotone loss decrease
    batch = {"x": rng.randn(8, D).astype("float32"),
             "label": rng.randn(8, D).astype("float32")}
    feeds = [batch] * 3

    mesh = make_mesh(pp=4, devices=jax.devices())

    def run_schedule(schedule):
        scope = pt.Scope()
        for n, v in snapshot.items():
            scope.set(n, jnp.asarray(v))
        trainer = PipelineTrainer(main, loss, bnames, mesh,
                                  n_microbatch=4, scope=scope,
                                  schedule=schedule)
        return [float(np.asarray(trainer.run(f))) for f in feeds]

    got = run_schedule("gpipe")
    got_1f1b = run_schedule("1f1b")
    np.testing.assert_allclose(got_1f1b, got, rtol=1e-6, atol=1e-7)

    main2, startup2, loss2, _ = build()
    scope2 = pt.Scope()
    with pt.scope_guard(scope2):
        exe2 = pt.Executor(pt.CPUPlace())
        exe2.run(startup2)
        for n, v in snapshot.items():
            scope2.set(n, jnp.asarray(v))
        expect = [float(np.asarray(exe2.run(
            main2, feed=f, fetch_list=[loss2])[0])) for f in feeds]

    np.testing.assert_allclose(got, expect, rtol=1e-5, atol=1e-6)
    assert got[-1] < got[0]
    print(f"RESULT pp-ok {nproc} {n_global}", flush=True)


if __name__ == "__main__":
    main()
