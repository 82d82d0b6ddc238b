"""ParallelExecutor — distributed training over the local mesh.

Parity: python/paddle/fluid/parallel_executor.py. The reference builds a
multi-GPU SSA graph with NCCL all-reduce nodes per gradient; here the
SAME traced step function is jitted with sharded inputs over the mesh —
XLA keeps global-batch semantics (loss/grads identical to single device)
and inserts the collectives over ICI itself.

Beyond plain dp, a DistributeTranspiler (parallel/transpiler.py — the
distribute_transpiler.py analog) can be attached: its sharding table is
applied to params AND optimizer state, giving Megatron tensor parallel
(tp axis) and ZeRO-style optimizer-state sharding (mode="zero", the
pserver analog) THROUGH this executor — the scope then holds genuinely
sharded jax.Arrays between steps.

Multi-host (after fleet.init → jax.distributed.initialize): the mesh
spans every process's devices; each host feeds its LOCAL batch (the
reference's per-trainer readers) and the feeds are assembled into
global arrays (host_local_array_to_global_array), so the global batch
is the concatenation over hosts on the dp axis; params materialize
shard-wise from each host's identically-seeded full copy. Tested by
tests/test_multihost.py::test_two_process_data_parallel_training
(2-process dp == single-process global-batch numerics).
"""
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.framework import default_main_program
from ..core.scope import global_scope
from ..core.trace import build_step_fn
from ..ops.registry import lowering_for
from ..core.dtypes import as_jnp_dtype
from .. import telemetry as _tm
from ..resilience import chaos as _chaos
from .mesh import local_mesh

from ..core.compiler import BuildStrategy, ExecutionStrategy  # noqa: F401

__all__ = ["ParallelExecutor", "BuildStrategy", "ExecutionStrategy"]


class ParallelExecutor:
    def __init__(self, use_cuda=True, loss_name=None, main_program=None,
                 share_vars_from=None, exec_strategy=None,
                 build_strategy=None, num_trainers=1, trainer_id=0,
                 scope=None, mesh=None, use_tpu=None, transpiler=None,
                 grad_sync=None, sparse=None):
        self.program = main_program or default_main_program()
        self.loss_name = loss_name
        self.scope = scope or global_scope()
        self.transpiler = transpiler
        if transpiler is not None:
            if transpiler.mesh is None:
                transpiler.transpile(program=self.program)
            self.mesh = transpiler.mesh
            self._shardings = transpiler.shardings()
        else:
            self.mesh = mesh if mesh is not None else local_mesh("dp")
            self._shardings = {}
        # like Executor(): with no mesh given it takes the process's
        # devices, and says which. use_cuda (the reference's default
        # True) is accepted and not interpreted; an explicit
        # use_tpu=True is a demand and fails without TPU devices.
        self.platform = self.mesh.devices.flat[0].platform
        if use_tpu and self.platform != "tpu":
            raise RuntimeError(
                f"ParallelExecutor(use_tpu=True): the mesh holds "
                f"{self.mesh.devices.size} {self.platform!r} device(s), "
                f"no TPU")
        # gradient-sync policy (parallel/gradsync.py): explicit arg >
        # PADDLE_TPU_GRAD_SYNC > minimize(grad_sync=...) program hint.
        # None keeps the implicit-XLA-all-reduce path bit-identical
        # (zero new fetches, state, collectives, or compile-key
        # entries — pinned by tests/test_gradsync.py).
        from . import gradsync as _gradsync
        self.grad_sync = _gradsync.resolve_policy(grad_sync,
                                                  program=self.program)
        # sparse-engine policy (parallel/sparse.py): only a program
        # that actually carries a distributed lookup table AND an
        # explicit opt-in (arg or PADDLE_TPU_SPARSE) ever imports the
        # engine — pinned by tests/test_bench_contract.py. The engine
        # runs the step under explicit shard_map, so it brings a
        # default fp32 gradsync policy for the dense params when none
        # is set.
        self.sparse_engine = None
        dist_tables = [
            op.inputs["W"][0]
            for op in self.program.global_block().ops
            if op.type == "lookup_table"
            and op.attrs.get("is_distributed")]
        if dist_tables:
            import os as _os
            spec = sparse if sparse is not None \
                else _os.environ.get("PADDLE_TPU_SPARSE")
            if spec is not None and str(spec).strip().lower() not in \
                    ("", "0", "off", "none", "false"):
                from . import sparse as _sparse
                pol = _sparse.parse_policy(spec)
                if transpiler is not None:
                    raise ValueError(
                        "the sparse engine owns its tables' sharding; "
                        "drop the DistributeTranspiler (its SPMD "
                        "row-sharding is the engine-off path) or the "
                        "sparse= policy")
                if self.grad_sync is None:
                    self.grad_sync = _gradsync.GradSyncPolicy("fp32")
                self.sparse_engine = _sparse.SparseEngine(
                    self.program, pol, self.mesh,
                    reduce=self.grad_sync.reduce)
        elif sparse is not None and str(sparse).strip().lower() not in \
                ("", "0", "off", "none", "false"):
            raise ValueError(
                "sparse= engine requested but the program has no "
                "distributed lookup table; build the embedding with "
                "is_distributed=True (and is_sparse=True)")
        if self.grad_sync is not None:
            if transpiler is not None:
                raise ValueError(
                    "grad_sync policies require pure data parallelism; "
                    "a DistributeTranspiler shards params/optimizer "
                    "state, which the explicit shard_map sync path "
                    "does not support — drop grad_sync or the "
                    "transpiler")
            if "dp" not in self.mesh.shape:
                raise ValueError(
                    "grad_sync policies need a 'dp' axis on the mesh")
        self._cache = {}
        self._step = 0
        # the placed (global) feed arrays of the most recent run(): how
        # the batch was actually sharded, readable by smoke checks
        self.last_feeds = {}
        # recompile-explainer state (telemetry on only): named fields
        # of every compile key seen, plus the latest explanation
        self._seen_fields = []
        self.last_recompile = None
        self._replicated = NamedSharding(self.mesh, P())
        # asynchronous step pipeline (tpupipe): same bounded in-flight
        # window as Executor.run(async_steps=k), over the shard_map /
        # SPMD path — run() returns PendingStep handles and defers the
        # global-fetch readback. 0/None keeps today's synchronous path.
        self.async_steps = None
        self._async_pipe = None

    @property
    def device_count(self):
        return int(np.prod([self.mesh.shape[a] for a in self.mesh.axis_names]))

    def _feed_sharding(self, arr, name=None):
        """Sharding for one HOST-LOCAL feed array (multi-process: the
        global batch is nproc local batches, which is what dp must
        divide)."""
        if arr.ndim == 0 or "dp" not in self.mesh.shape:
            return self._replicated
        if self.transpiler is not None:
            # single source of truth: the transpiler's policy (dp batch
            # axis + sp time axis; see transpiler.feed_sharding)
            return self.transpiler.feed_sharding(arr.shape, name=name)
        dp = self.mesh.shape.get("dp", 1)
        dp_ok = (arr.shape[0] * jax.process_count()) % dp == 0
        if not dp_ok and dp > 1:
            if jax.process_count() > 1:
                # replication can't represent divergent per-host
                # batches — assembling them as "replicated" would make
                # hosts silently compute different gradients
                raise RuntimeError(
                    f"feed batch {arr.shape[0]} x "
                    f"{jax.process_count()} hosts does not divide "
                    f"dp={dp}; pad the local batch (multi-host feeds "
                    "cannot fall back to replication)")
            import warnings
            warnings.warn(
                f"feed batch {arr.shape[0]} does not divide dp={dp}; "
                "replicating this feed (no data parallelism for it)")
        return NamedSharding(self.mesh, P("dp" if dp_ok else None,
                                          *([None] * (arr.ndim - 1))))

    def _param_sharding(self, name):
        return self._shardings.get(name, self._replicated)

    def _feed_to_global(self, arr, sharding):
        """Place one host-side feed array. Single-process: plain
        device_put. Multi-process: `arr` is this HOST's local batch;
        assemble the global array (global batch = hosts' batches
        concatenated along the sharded axes — the per-trainer reader
        semantics). Feeds whose sharding is fully replicated must be
        host-identical (e.g. constants); that is the caller's contract,
        like the reference's broadcast-once parameters."""
        if jax.process_count() == 1:
            return jax.device_put(arr, sharding)
        from jax.experimental import multihost_utils
        return multihost_utils.host_local_array_to_global_array(
            np.asarray(arr), self.mesh, sharding.spec)

    def _param_to_global(self, val, sharding):
        """Place one persistable. Multi-process: every host holds an
        identically-seeded full copy; each materializes only its
        addressable shards."""
        if jax.process_count() == 1:
            return jax.device_put(val, sharding)
        if isinstance(val, jax.Array) and not val.is_fully_addressable:
            return val
        v = np.asarray(val)
        return jax.make_array_from_callback(v.shape, sharding,
                                            lambda idx: v[idx])

    def _gradsync_prepare(self, program, persist, persist_sh):
        """Bucket plan + error-feedback state for the active grad_sync
        policy, plus the is_sparse tap list. Seeds `gradsync.ef.<bucket>`
        residuals (zeros) in the scope on first use and adds them to the
        persist set with dp sharding, so they ride the executor's
        existing donate/sharding path like any other state.

        Sparse row grads are SKIPPED by the bucketed/quantized wire —
        they belong to the sparse engine. Engine-owned tables handle
        their own exchange; any remaining (replicated) is_sparse table
        gets its taps returned so the grad transform can all-gather
        ids+row-grads over dp, keeping the tail's row-sparse update
        identical on every member."""
        from . import gradsync
        policy = self.grad_sync
        bops = [op for op in program.global_block().ops
                if op.type == "backward_macro"]
        if not bops:
            return [], []
        bop = bops[0]
        engine_tables = set(self.sparse_engine.tables) \
            if self.sparse_engine is not None else set()
        sparse_taps = [
            {"ids": tap["ids"], "delta": tap["delta"]}
            for spec in bop.attrs.get("sparse_params", [])
            if spec["param"] not in engine_tables
            for tap in spec["taps"]]
        named = [(n, tuple(persist[n].shape), persist[n].dtype)
                 for n in bop.attrs["param_names"]]
        plan = gradsync.plan_buckets(named, policy.bucket_bytes,
                                     policy.block_size)
        dp = self.mesh.shape.get("dp", 1)
        sh = NamedSharding(self.mesh, P("dp"))
        for name, local_len in gradsync.state_entries(plan, policy):
            val = self.scope.get(name)
            if val is None or tuple(val.shape) != (dp * local_len,):
                val = np.zeros((dp * local_len,), np.float32)
                self.scope.set(name, val)
            persist_sh[name] = sh
            persist[name] = self._param_to_global(val, sh)
            if _tm.memledger_enabled():
                # creation site of the error-feedback residuals — the
                # per-step classify keeps them attributed as they are
                # donated/recreated, this seeds the first sample
                from ..telemetry import memledger as _ml
                _ml.register("gradsync_ef", name, persist[name],
                             mode=policy.mode)
        return plan, sparse_taps

    def _build_gradsync_fn(self, program, fetch_names, is_test,
                           feed_arrays, feed_sh, persist, persist_sh,
                           plan, sparse_taps=()):
        """The explicit-sync path: the SAME traced step runs under
        shard_map over the dp axis (per-member local compute) and
        gradsync.sync_gradients performs the dp reduction with
        explicit — bucketed / quantized / overlappable — collectives.
        When the sparse engine is active it rides the same shard_map:
        its lookup/update ops dispatch through the engine
        (build_step_fn sparse_engine hook) and its sharded tables /
        stale rings keep their dp layout through out_specs.

        Fetch semantics: fetches whose leading dim is the local batch
        stay dp-sharded (reassembling to the global batch axis, exactly
        like the implicit path); other fetches are globalized with
        pmean for floats (exact for the batch-`mean` losses this path
        assumes — set reduce=sum in the policy for sum losses) and psum
        for integers (count-like fetches). Per-member RNG is
        decorrelated by folding the dp index into the step key (the
        reference's per-trainer seeds)."""
        from . import gradsync
        policy = self.grad_sync
        engine = self.sparse_engine
        mesh = self.mesh
        dp = mesh.shape.get("dp", 1)

        step = build_step_fn(
            program, fetch_names, is_test, None,
            grad_transform=gradsync.make_grad_transform(
                policy, plan, dp, sparse_taps=sparse_taps),
            sparse_engine=engine)

        persist_specs = {n: persist_sh[n].spec for n in persist}
        feed_specs = {k: feed_sh[k].spec for k in feed_arrays}

        def local_aval(arr, spec):
            shape = list(arr.shape)
            for i, ax in enumerate(spec):
                if ax is None:
                    continue
                for nm in (ax if isinstance(ax, tuple) else (ax,)):
                    shape[i] //= mesh.shape[nm]
            return jax.ShapeDtypeStruct(tuple(shape), arr.dtype)

        la_persist = {n: local_aval(persist[n], persist_specs[n])
                      for n in persist}
        la_feed = {k: local_aval(feed_arrays[k], feed_specs[k])
                   for k in feed_arrays}

        # classify fetches via an axis-free structural probe: the real
        # transform's collectives need the dp axis bound, so eval_shape
        # runs with shape-preserving stand-ins instead (identity
        # collectives in both the gradsync transform and the engine)
        probe = build_step_fn(
            program, fetch_names, is_test, None,
            grad_transform=gradsync.make_probe_transform(
                policy, plan, dp, sparse_taps=sparse_taps),
            sparse_engine=engine.probe_clone() if engine else None)
        f_avals, p_avals = jax.eval_shape(probe, la_persist, la_feed,
                                          jax.random.PRNGKey(0))

        batch_dims = set()
        for k in feed_arrays:
            ents = list(feed_specs[k])
            if ents and ents[0] is not None and "dp" in (
                    ents[0] if isinstance(ents[0], tuple)
                    else (ents[0],)):
                batch_dims.add(la_feed[k].shape[0])
        fetch_specs = []
        fetch_kind = []
        for av in f_avals:
            if av.ndim >= 1 and av.shape[0] in batch_dims:
                fetch_specs.append(P(*(["dp"] + [None] * (av.ndim - 1))))
                fetch_kind.append("batch")
            elif jnp.issubdtype(av.dtype, jnp.floating):
                fetch_specs.append(P())
                fetch_kind.append("mean")
            else:
                fetch_specs.append(P())
                fetch_kind.append("sum")
        def persist_out_spec(n):
            if n.startswith(gradsync.EF_PREFIX):
                return P("dp")
            if engine is not None and (
                    n in engine.row_var_names
                    or n in engine.state_names):
                return engine.out_spec(n) if n not in persist_specs \
                    else persist_specs[n]
            return P()

        out_persist_specs = {n: persist_out_spec(n) for n in p_avals}

        def mapped(persist_in, feed_in, key_in):
            key_in = jax.random.fold_in(key_in,
                                        jax.lax.axis_index("dp"))
            # fully-manual shard_map: per-member code, kernels allowed
            with lowering_for(self.platform):
                fetches, new_persist = step(persist_in, feed_in, key_in)
            out = []
            for f, kind in zip(fetches, fetch_kind):
                if kind == "mean":
                    f = jax.lax.pmean(f, "dp")
                elif kind == "sum" and f.dtype != jnp.bool_:
                    f = jax.lax.psum(f, "dp")
                out.append(f)
            return out, new_persist

        sm = jax.shard_map(mapped, mesh=mesh,
                           in_specs=(persist_specs, feed_specs, P()),
                           out_specs=(fetch_specs, out_persist_specs),
                           check_vma=False)
        return jax.jit(sm, donate_argnums=(0,))

    # ------------------------------------------------ async pipeline
    def drain(self):
        """Materialize every in-flight async step (see Executor.drain)."""
        if self._async_pipe is not None:
            self._async_pipe.drain()
        return self

    def discard_pending(self):
        """Abandon in-flight async steps without materializing them."""
        if self._async_pipe is not None:
            return self._async_pipe.discard()
        return 0

    @property
    def inflight(self):
        return len(self._async_pipe) if self._async_pipe is not None \
            else 0

    def _finalize_record(self, rec):
        """Deferred tail of an async pexe step: block, read the global
        fetches back, and emit the completion-side telemetry (fleet
        heartbeat, sparse-engine gauges) with that step's numbers."""
        fetches = rec["fetches"]
        if rec["deferred"]:
            t_w = time.perf_counter()
            with _tm.span("pexe.pending_wait", step=rec["step"]):
                jax.block_until_ready(fetches)
            if rec["tm_on"]:
                _tm.histogram("pexe.pending_wait_seconds").observe(
                    time.perf_counter() - t_w)
                _tm.fleet.on_step(rec["dt"])
                if rec["engine"] is not None:
                    rec["engine"].update_gauges(self.scope)
        if rec["return_numpy"]:
            return [np.asarray(f) for f in fetches]
        return fetches

    # ------------------------------------------------------------------
    def _mesh_context(self, fetch_names=(), feed_names=(),
                      memory_cap_bytes=None):
        """This executor's config as a meshlint MeshLintContext — the
        object verify() lints and tools/tpulint.py serializes. Imports
        meshlint, so only validate-on paths may call it (bench pin)."""
        from ..analysis.meshlint import MeshLintContext
        param_specs = {n: tuple(sh.spec)
                       for n, sh in self._shardings.items()}
        return MeshLintContext(
            self.mesh,
            program=self.program,
            fetch_names=fetch_names,
            feed_names=feed_names,
            donate_state=True,        # donate_argnums=(0,) below
            async_steps=self.async_steps,
            grad_sync=self.grad_sync,
            sparse=(self.sparse_engine.policy
                    if self.sparse_engine is not None else None),
            param_specs=param_specs,
            memory_cap_bytes=memory_cap_bytes,
            label="ParallelExecutor")

    def verify(self, fetch_list=None, feed_names=(), passes=None,
               raise_on_error=True, memory_cap_bytes=None):
        """Static pre-trace verification of this executor's sharded
        config: proglint over the Program (use-before-def, shapes,
        hazards) plus the meshlint passes (mesh specs, collective
        consistency, donation aliasing, device footprint, recompile
        hazards). Runs automatically on each
        compile when PADDLE_TPU_VALIDATE=1 (or run(validate=True));
        callable directly for lint-only flows (tools/tpulint.py).
        Returns the combined diagnostics list."""
        from ..analysis import run_passes as _run_prog
        from ..analysis.diagnostics import ProgramVerificationError
        from ..analysis.meshlint import run_mesh_passes
        fetch_names = tuple(f.name if hasattr(f, "name") else f
                            for f in (fetch_list or ()))
        diags = list(_run_prog(self.program, fetch_list=fetch_names,
                               feed_names=feed_names))
        diags += run_mesh_passes(self._mesh_context(
            fetch_names=fetch_names, feed_names=feed_names,
            memory_cap_bytes=memory_cap_bytes), passes=passes)
        if raise_on_error and any(d.severity == "error" for d in diags):
            raise ProgramVerificationError(
                [d for d in diags if d.severity == "error"])
        return diags

    def run(self, fetch_list=None, feed=None, feed_dict=None,
            return_numpy=True, is_test=False, async_steps=None,
            validate=None):
        # the parent of pexe.step and pexe.pending_wait
        with _tm.span("pexe.run", step=self._step,
                      program=self.program._version):
            return self._run(fetch_list, feed, feed_dict, return_numpy,
                             is_test, async_steps, validate)

    def _run(self, fetch_list, feed, feed_dict, return_numpy, is_test,
             async_steps, validate):
        from ..core.executor import resolve_async_steps
        k_async = resolve_async_steps(async_steps, self.async_steps)
        feed = dict(feed or feed_dict or {})
        fetch_names = [f.name if hasattr(f, "name") else f
                       for f in (fetch_list or [])]
        program = self.program
        # per-rank telemetry (one flag check when off): pexe.* metrics
        # carry the process-index label via the registry default-labels
        # hook fleet.init installs — same metric names on every rank
        tm_on = _tm.enabled()
        t_run0 = time.perf_counter()

        seed = program.random_seed
        key = jax.random.fold_in(jax.random.PRNGKey(seed), self._step)
        self._step += 1
        # chaos: the SAME executor.step injection point the plain
        # Executor honors (step_fail / rank_lost / resize fire under
        # SPMD training too — the elastic selftest's kill target).
        # One cached-bool check when disarmed.
        if _chaos.armed():
            _chaos.check("executor.step",
                         detail=f"pexe step {self._step - 1}",
                         step=self._step - 1)

        feed_arrays = {}
        feed_sh = {}
        for k, v in feed.items():
            if isinstance(v, jax.Array) and not v.is_fully_addressable:
                # already a global array (e.g. a return_numpy=False
                # fetch): pass through with its own sharding
                feed_arrays[k] = v
                feed_sh[k] = v.sharding
                continue
            var = program.global_block().vars.get(k)
            dt = as_jnp_dtype(var.dtype) if var is not None else None
            # stay on host until placement — a jnp cast here would add
            # a device->host round-trip before the global assembly
            arr = np.asarray(v)
            if dt is not None and arr.dtype != np.dtype(dt):
                arr = arr.astype(dt)
            # single-process non-divisible batches fall back to
            # replication inside feed_sharding (slice_variable
            # remainder analog); multi-process they raise there
            sh = self._feed_sharding(arr, name=k)
            feed_sh[k] = sh
            feed_arrays[k] = self._feed_to_global(arr, sh)

        self.last_feeds = feed_arrays

        engine = self.sparse_engine
        engine_rows = set(engine.row_var_names) if engine else ()
        persist = {}
        persist_sh = {}
        for v in program.persistable_vars():
            if v.name in engine_rows:
                continue           # mod-sharded by the engine below
            val = self.scope.get(v.name)
            if val is None:
                raise RuntimeError(
                    f"persistable var {v.name!r} not initialized; run the "
                    f"startup program on a plain Executor first")
            sh = self._param_sharding(v.name)
            persist_sh[v.name] = sh
            persist[v.name] = self._param_to_global(val, sh)
        if engine is not None:
            dp = self.mesh.shape.get("dp", 1)

            def local_shape(k):
                shape = list(feed_arrays[k].shape)
                spec = tuple(feed_sh[k].spec)
                if shape and spec and spec[0] is not None:
                    shape[0] //= dp
                return tuple(shape)

            engine.plan_run({k: local_shape(k) for k in feed_arrays})
            engine.prepare_persist(persist, persist_sh, self.scope)
            for name, gshape, dt, spec, fill in engine.state_entries():
                sh = NamedSharding(self.mesh, spec)
                val = self.scope.get(name)
                if val is None or tuple(val.shape) != tuple(gshape):
                    val = np.full(gshape, fill, dt)
                    self.scope.set(name, val)
                persist_sh[name] = sh
                persist[name] = self._param_to_global(val, sh)

        policy = self.grad_sync
        gs_plan = gs_taps = None
        if policy is not None:
            gs_plan, gs_taps = self._gradsync_prepare(program, persist,
                                                      persist_sh)

        sig = tuple(sorted((k, v.shape, str(v.dtype))
                           for k, v in feed_arrays.items()))
        from ..core import trace as _trace
        ckey = (id(program), program._version, sig, tuple(fetch_names),
                bool(is_test), _trace.FUSE_OPTIMIZER_TAIL,
                _trace.FUSE_MAX_ELEMS)
        if policy is not None:
            # only the policy-on path may grow the compile key (the
            # off path stays byte-for-byte the historical tuple)
            ckey = ckey + (policy.key(),)
        if engine is not None:
            ckey = ckey + (engine.key(),)
        fn = self._cache.get(ckey)
        # a new key compiles on its first call: the compile log puts
        # that down to this program, as Executor.run does
        own = _tm.compile_owner(f"executor:{program._version}") \
            if fn is None else _tm.compiles.NO_OWNER
        if fn is None:
            # opt-in pre-trace verification gate (same tri-state as
            # Executor.run: validate= arg > PADDLE_TPU_VALIDATE env):
            # proglint + meshlint once per compile, so a bad spec
            # surfaces as a ProgramVerificationError with a named pass
            # instead of a _SpecError stack from inside the trace. Cache
            # hits (and the default validate-off path) never import
            # meshlint.
            from ..core.executor import Executor as _Exec
            if _Exec._validate_requested(validate):
                self.verify(fetch_list=fetch_names,
                            feed_names=list(feed_arrays))
            if tm_on:
                _tm.counter("pexe.compile_count").inc()
                _tm.gauge("pexe.device_count").set(self.device_count)
                # tpuscope recompile explainer: name the ckey
                # component (shape bucket, grad_sync policy, engine
                # key, ...) that busted the cache
                from ..telemetry import attribution as _attr
                fields = _attr.pexe_ckey_fields(
                    ckey,
                    policy_key=policy.key() if policy else None,
                    engine_key=engine.key() if engine else None)
                if self._seen_fields:
                    self.last_recompile = _attr.explain_recompile(
                        "pexe", fields, self._seen_fields,
                        step=self._step - 1)
                self._seen_fields.append(fields)
            if policy is not None:
                fn = self._build_gradsync_fn(
                    program, fetch_names, is_test, feed_arrays, feed_sh,
                    persist, persist_sh, gs_plan,
                    sparse_taps=gs_taps or ())
                self._cache[ckey] = fn
            else:
                step_fn = build_step_fn(program, fetch_names, is_test,
                                        None)

                def wrapped(persist_in, feed_in, key_in, _step=step_fn,
                            _sh=dict(persist_sh)):
                    # this jit is partitioned by GSPMD, which cannot
                    # split a Mosaic custom call: more than one device
                    # keeps the partitionable jnp compositions
                    with lowering_for(self.platform,
                                      partitioned=self.device_count > 1):
                        fetches, new_persist = _step(
                            persist_in, feed_in, key_in)
                    # pin state outputs to their input layout so the
                    # scope keeps genuinely sharded arrays between
                    # steps (tp/ZeRO)
                    new_persist = {
                        n: jax.lax.with_sharding_constraint(v, _sh[n])
                        if n in _sh else v
                        for n, v in new_persist.items()}
                    return fetches, new_persist

                fn = jax.jit(
                    wrapped,
                    in_shardings=(persist_sh, dict(feed_sh),
                                  self._replicated),
                    donate_argnums=(0,))
                self._cache[ckey] = fn
                _tm.compiles.register_program(
                    f"executor:{program._version}", fn,
                    (persist, feed_arrays, key),
                    program.name_scopes())
        elif tm_on:
            _tm.counter("pexe.cache_hit_count").inc()

        with own, _tm.span("pexe.step", step=self._step - 1,
                           devices=self.device_count):
            try:
                fetches, new_persist = fn(persist, feed_arrays, key)
            except Exception as e:
                if _tm.memledger_enabled():
                    from ..telemetry import memledger as _ml
                    _ml.handle_possible_oom(
                        e, context={"site": "pexe.step",
                                    "step": self._step - 1,
                                    "devices": self.device_count})
                raise
        if k_async > 0:
            # a fetch that is ALSO a persistable output may alias the
            # state buffer the next queued step donates — give pending
            # fetches their own buffer (async only; see Executor.run)
            fetches = [jnp.copy(f) if n in new_persist else f
                       for n, f in zip(fetch_names, fetches)]
        for name, val in new_persist.items():
            self.scope.set(name, val)
        if _tm.memledger_enabled():
            # attribute the global (sharded) state: gradsync.ef.* and
            # optimizer slots classify by name, engine rows + engine
            # state are the sparse_table bucket; feeds are transient
            from ..telemetry import memledger as _ml
            sparse_names = set(engine_rows)
            if engine is not None:
                sparse_names.update(
                    n for n, *_rest in engine.state_entries())
            for _n, _v in new_persist.items():
                cat = ("sparse_table" if _n in sparse_names
                       else _ml.classify_persist_name(_n))
                _ml.register(cat, _n, _v)
            for _n, _v in feed_arrays.items():
                _ml.register("feed", _n, _v)
            _ml.on_step(step=self._step - 1,
                        context={"site": "pexe.step",
                                 "devices": self.device_count})
        dt = time.perf_counter() - t_run0
        if tm_on:
            _tm.counter("pexe.steps").inc()
            _tm.histogram("pexe.step_seconds").observe(dt)
            # completion-side accounting (heartbeat, engine gauges)
            # defers to materialization in async mode
            if k_async == 0:
                _tm.fleet.on_step(dt)
                if engine is not None:
                    engine.update_gauges(self.scope)
        rec = {"step": self._step - 1, "fetches": fetches,
               "fetch_names": fetch_names, "return_numpy": return_numpy,
               "tm_on": tm_on, "dt": dt, "engine": engine,
               "deferred": k_async > 0}
        if k_async > 0:
            from ..core.pipeline_exec import PendingStep, StepWindow
            if tm_on:
                _tm.counter("pexe.async_steps").inc()
            pipe = self._async_pipe
            if pipe is None:
                pipe = self._async_pipe = StepWindow(
                    k_async, gauge_name="pexe.inflight")
            pipe.depth = max(1, k_async)
            return pipe.push(PendingStep(pipe, rec,
                                         self._finalize_record))
        return self._finalize_record(rec)
