"""Inference engine.

Parity: paddle/fluid/inference/{api,analysis}/ — the reference's C++
NativePredictor/AnalysisPredictor with graph passes. TPU-native: the
pruned inference Program is jitted once per input signature with donated
output buffers disabled (read-only params), bf16 precision optional, and
an AOT serialize/deserialize path via jax.jit(...).lower().compile().
"""
import threading
import time

import numpy as np
import jax
import jax.numpy as jnp

from . import telemetry as _tm
from .core.executor import Executor
from .core.place import core_place_of
from .core.scope import Scope, scope_guard
from .core.trace import build_step_fn
from .core.dtypes import as_jnp_dtype
from . import io as _io

__all__ = ["InferenceEngine", "AnalysisConfig", "CompiledPredictor",
           "bucket_feed", "next_bucket", "default_buckets"]


def default_buckets(max_batch_size):
    """Power-of-two batch buckets up to (and including) max_batch_size:
    64 -> (1, 2, 4, 8, 16, 32, 64). On TPU every distinct feed shape is
    a fresh XLA compile, so bounding the batch dim to this set bounds
    the number of compiled signatures to log2(max)+1."""
    max_batch_size = int(max_batch_size)
    if max_batch_size < 1:
        raise ValueError("max_batch_size must be >= 1")
    out, b = [], 1
    while b < max_batch_size:
        out.append(b)
        b *= 2
    out.append(max_batch_size)
    return tuple(out)


def next_bucket(n, buckets):
    """Smallest bucket >= n, or raise when n exceeds every bucket."""
    for b in sorted(buckets):
        if n <= b:
            return int(b)
    raise ValueError(
        f"batch of {n} rows exceeds the largest bucket {max(buckets)}")


def bucket_feed(feed, buckets, axis=0):
    """Pad every array's batch dim up to the next shape bucket.

    Returns ``(padded_feed, true_rows, mask)`` where `mask` is a bool
    vector of length `bucket` that is True for real rows. Padding is
    zeros, so row-wise inference graphs (fc/conv/softmax over the
    feature axes) produce identical results for the real rows; callers
    slice fetches back with ``out[:true_rows]``.

    This is the standalone half of the serving batcher's recompile fix:
    direct `InferenceEngine.run(feed, batch_bucket=buckets)` callers go
    through the same helper, so the per-signature jit cache sees at
    most `len(buckets)` batch shapes instead of one per request size.
    """
    if not feed:
        return {}, 0, np.zeros((0,), dtype=bool)
    arrays = {k: np.asarray(v) for k, v in feed.items()}
    rows = {k: (a.shape[axis] if a.ndim > axis else None)
            for k, a in arrays.items()}
    sizes = set(r for r in rows.values() if r is not None)
    if len(sizes) != 1:
        raise ValueError(f"feed arrays disagree on batch dim {axis}: "
                         f"{rows}")
    n = sizes.pop()
    bucket = next_bucket(n, buckets)
    mask = np.arange(bucket) < n
    if bucket == n:
        return arrays, n, mask
    padded = {}
    for k, a in arrays.items():
        if rows[k] is None:
            padded[k] = a
            continue
        pad_shape = list(a.shape)
        pad_shape[axis] = bucket - n
        padded[k] = np.concatenate(
            [a, np.zeros(pad_shape, dtype=a.dtype)], axis=axis)
    return padded, n, mask


class AnalysisConfig:
    """Accepted for API parity with the reference predictor config."""

    def __init__(self, model_dir=None):
        self.model_dir = model_dir
        self.use_bf16 = False
        self.device_id = 0

    def enable_bf16(self):
        self.use_bf16 = True
        return self

    # reference names
    def enable_use_gpu(self, *a, **k):
        return self

    def switch_ir_optim(self, *a, **k):
        return self


class InferenceEngine:
    """Load-once, compile-per-signature predictor.

    usage:
        eng = InferenceEngine.from_dir('/path')   # save_inference_model dir
        out = eng.run({'img': x})
    """

    def __init__(self, program, feed_names, fetch_vars, scope, place=None,
                 use_bf16=False):
        self.program = program
        self.feed_names = list(feed_names)
        self.fetch_names = [v.name if hasattr(v, "name") else v
                            for v in fetch_vars]
        self.scope = scope
        self.place = core_place_of(place)
        self._cache = {}
        # single-flight compile guard: _lock protects _cache/_inflight
        # membership; _inflight maps signature -> Event the compiling
        # thread sets when its entry lands in _cache (see _get_fn)
        self._lock = threading.Lock()
        self._inflight = {}
        if use_bf16:
            from .amp import cast_program_to_bf16, cast_params_to_bf16
            cast_program_to_bf16(self.program)
            cast_params_to_bf16(self.program, self.scope)
        self._persist = {v.name: self.scope.get(v.name)
                         for v in self.program.persistable_vars()
                         if self.scope.get(v.name) is not None}

    @classmethod
    def from_dir(cls, dirname, place=None, config=None):
        scope = Scope()
        exe = Executor(place)
        with scope_guard(scope):
            program, feeds, fetches = _io.load_inference_model(dirname, exe)
        return cls(program, feeds, fetches, scope, place,
                   use_bf16=bool(config and config.use_bf16))

    def _signature(self, feed):
        return tuple(sorted((k, tuple(np.shape(v))) for k, v in feed.items()))

    def signature_count(self):
        """Number of distinct compiled feed signatures (jit entries)."""
        return len(self._cache)

    def params(self):
        """{name: device array} of the loaded persistable parameters
        (no copy). This is the official seam for building sibling
        executables over the same checkpoint — e.g. the serving decode
        tier (`serving.decode.DecodeEngine.from_inference_engine`)
        shares these arrays with the full-program predict path."""
        return dict(self._persist)

    def feed_specs(self):
        """{feed_name: (shape, dtype_str)} from the program's data vars
        (batch dim reported as -1). Serving uses this to build warmup
        feeds and to coerce JSON tensors."""
        specs = {}
        block = self.program.global_block()
        for n in self.feed_names:
            var = block.vars.get(n)
            if var is None:
                specs[n] = ((-1,), "float32")
            else:
                shape = tuple(var.shape) if var.shape else (-1,)
                specs[n] = (shape, var.dtype)
        return specs

    def _compile_fn(self, sig):
        """Build + cache the jitted step for `sig`; caller holds the
        single-flight leadership for this signature. The trace/compile
        is retried under the resilience policy: a remote compile
        service can flake (UNAVAILABLE / deadline) — transient
        failures (incl. the inference.compile chaos point)
        are absorbed, real trace errors classify fatal and surface
        unchanged."""
        from .resilience import chaos as _chaos
        from .resilience import retry as _retry
        if _tm.enabled():
            _tm.counter("inference.compile_count").inc()

        def _build():
            if _chaos.armed():
                _chaos.check("inference.compile")
            step = build_step_fn(self.program, self.fetch_names,
                                 is_test=True, place=self.place)

            def infer(persist, feed_arrays):
                fetches, _ = step(persist, feed_arrays,
                                  jax.random.PRNGKey(0))
                return fetches

            return jax.jit(infer)

        with _tm.span("inference.compile", signatures=len(self._cache)):
            fn = _retry.call(
                _build, name="inference.compile",
                policy=_retry.RetryPolicy(max_attempts=3,
                                          base_delay_s=0.1,
                                          max_delay_s=2.0))
        self._cache[sig] = fn
        if _tm.enabled():
            _tm.gauge("inference.signature_count").set(len(self._cache))
        return fn

    def _get_fn(self, feed):
        sig = self._signature(feed)
        fn = self._cache.get(sig)
        if fn is not None:
            if _tm.enabled():
                _tm.counter("inference.cache_hit_count").inc()
            return fn
        # single-flight: exactly one thread traces/compiles a new
        # signature; concurrent callers of the same signature wait on
        # its Event instead of duplicate-compiling (the plain-dict race
        # this replaces compiled once per racing thread)
        while True:
            with self._lock:
                fn = self._cache.get(sig)
                if fn is not None:
                    if _tm.enabled():
                        _tm.counter("inference.cache_hit_count").inc()
                    return fn
                event = self._inflight.get(sig)
                if event is None:
                    event = threading.Event()
                    self._inflight[sig] = event
                    leader = True
                else:
                    leader = False
            if leader:
                try:
                    return self._compile_fn(sig)
                finally:
                    with self._lock:
                        self._inflight.pop(sig, None)
                    event.set()
            if _tm.enabled():
                _tm.counter("inference.compile_dedup_count").inc()
            event.wait()
            # leader either cached the fn (normal path, next loop
            # iteration returns it) or raised — then the first waiter
            # to re-take the lock becomes the new leader and retries

    def run(self, feed, return_numpy=True, batch_bucket=None):
        """Run one inference request.

        batch_bucket: optional sequence of batch-size buckets. The feed
        is padded up to the next bucket (see `bucket_feed`) before the
        jit-cache lookup and fetches are sliced back to the true row
        count, so arbitrary request sizes reuse at most len(buckets)
        compiled signatures.
        """
        t0 = time.perf_counter()
        true_rows = bucket = None
        if batch_bucket is not None:
            feed, true_rows, _mask = bucket_feed(feed, batch_bucket)
            bucket = len(_mask)
        with _tm.span("inference.run", feeds=len(feed)):
            feed_arrays = {}
            for k, v in feed.items():
                var = self.program.global_block().vars.get(k)
                dt = as_jnp_dtype(var.dtype) if var is not None else None
                feed_arrays[k] = jnp.asarray(np.asarray(v), dtype=dt)
            outs = self._get_fn(feed_arrays)(self._persist, feed_arrays)
            if return_numpy:
                outs = [np.asarray(o) for o in outs]
        if true_rows is not None and true_rows != bucket:
            # slice padded rows off every batch-major fetch; fetches
            # without the batch dim (reductions) pass through whole
            outs = [o[:true_rows]
                    if getattr(o, "ndim", 0) >= 1 and o.shape[0] == bucket
                    else o for o in outs]
        if _tm.enabled():
            _tm.counter("inference.requests").inc()
            _tm.histogram("inference.latency_seconds").observe(
                time.perf_counter() - t0)
        return outs

    # ------------------------------------------------------------------
    def _zero_feed(self, feed_shapes, dtypes=None):
        feed = {}
        for k, shape in feed_shapes.items():
            var = self.program.global_block().vars.get(k)
            dt = as_jnp_dtype((dtypes or {}).get(
                k, var.dtype if var is not None else "float32"))
            feed[k] = jnp.zeros(shape, dtype=dt)
        return feed

    def compile(self, feed_shapes, dtypes=None):
        """AOT-compile for given {name: shape}; returns cost analysis.
        (ref inference analysis pass / AOT story)."""
        feed = self._zero_feed(feed_shapes, dtypes)
        fn = self._get_fn(feed)
        lowered = jax.jit(
            lambda p, f: fn(p, f)).lower(self._persist, feed)
        compiled = lowered.compile()
        cost = compiled.cost_analysis() or {}
        return {"flops": cost.get("flops"),
                "bytes accessed": cost.get("bytes accessed"),
                "signature": sorted(feed_shapes.items())}

    def save_compiled(self, dirname, feed_shapes, dtypes=None):
        """Serialize the AOT-lowered inference function (StableHLO via
        jax.export) + params to `dirname` — the reference's "serialized
        inference program + weights" deployment artifact
        (paddle/fluid/inference/api). Reload with load_compiled; the
        reloaded module runs WITHOUT the Program/tracer machinery."""
        import json
        import os
        from jax import export as jexport
        os.makedirs(dirname, exist_ok=True)
        feed = self._zero_feed(feed_shapes, dtypes)
        step = build_step_fn(self.program, self.fetch_names, is_test=True,
                             place=self.place)

        def infer(persist, feed_arrays):
            fetches, _ = step(persist, feed_arrays, jax.random.PRNGKey(0))
            return fetches

        exp = jexport.export(jax.jit(infer))(self._persist, feed)
        with open(os.path.join(dirname, "module.stablehlo"), "wb") as f:
            f.write(exp.serialize())
        # npz has no bfloat16: store bf16 params as a uint16 view and
        # record the true dtype so load_compiled can view them back
        params, param_dtypes = {}, {}
        for k, v in self._persist.items():
            a = np.asarray(v)
            param_dtypes[k] = str(a.dtype)
            if a.dtype.kind not in "biufc":
                a = a.view(np.uint16 if a.dtype.itemsize == 2
                           else np.uint8 if a.dtype.itemsize == 1
                           else np.uint32)
            params[k] = a
        np.savez(os.path.join(dirname, "params.npz"), **params)
        with open(os.path.join(dirname, "signature.json"), "w") as f:
            json.dump({"feeds": {k: list(v.shape) for k, v in feed.items()},
                       "dtypes": {k: str(v.dtype) for k, v in feed.items()},
                       "param_dtypes": param_dtypes,
                       "fetches": self.fetch_names}, f)
        try:
            self._save_native_artifact(dirname, feed, step)
        except Exception as e:  # pragma: no cover - version drift guard
            # the native artifact rides private jax internals for the
            # CompileOptions proto; its failure must never take down
            # the primary (module.stablehlo + params) artifact
            import warnings
            warnings.warn(f"native artifact not written: {e!r}")
        return dirname

    def _save_native_artifact(self, dirname, feed, step):
        """The NATIVE deployment artifact (consumed by the C predictor,
        native/predictor.cc — the analog of the reference's C++
        inference API, paddle/fluid/inference/api/analysis_predictor.h):

        - module.mlir: textual StableHLO of the inference function with
          the parameters baked in as CONSTANTS, so the module's only
          arguments are the feeds (sorted by name) and its results are
          the fetches (fetch_names order) — no param plumbing in C;
        - native_manifest.txt: line-based io spec (no JSON parser
          needed in C);
        - compile_options.pb: serialized CompileOptionsProto for
          PJRT_Client_Compile, written here where the XLA python is
          available so the C side stays proto-free.
        """
        import os
        from jax._src import compiler as jcompiler
        persist_const = {k: np.asarray(v) for k, v in self._persist.items()}
        feed_names = sorted(feed)

        def flat_infer(*args):
            # step returns fetches already ordered by fetch_names
            fetches, _ = step(persist_const, dict(zip(feed_names, args)),
                              jax.random.PRNGKey(0))
            return tuple(fetches)

        args = [feed[n] for n in feed_names]
        lowered = jax.jit(flat_infer).lower(*args)
        with open(os.path.join(dirname, "module.mlir"), "w") as f:
            f.write(str(lowered.compiler_ir(dialect="stablehlo")))
        try:  # the lowering already knows its output avals
            out_shapes = [o.aval for o in lowered.out_info]
        except Exception:
            out_shapes = jax.eval_shape(flat_infer, *args)
        lines = ["format ptpu-native-v1", f"inputs {len(feed_names)}"]
        for n in feed_names:
            a = feed[n]
            lines.append(f"{n} {a.dtype} {a.ndim} "
                         + " ".join(str(d) for d in a.shape))
        lines.append(f"outputs {len(self.fetch_names)}")
        for n, s in zip(self.fetch_names, out_shapes):
            lines.append(f"{n} {s.dtype} {len(s.shape)} "
                         + " ".join(str(d) for d in s.shape))
        with open(os.path.join(dirname, "native_manifest.txt"), "w") as f:
            f.write("\n".join(lines) + "\n")
        opts = jcompiler.get_compile_options(num_replicas=1,
                                             num_partitions=1)
        with open(os.path.join(dirname, "compile_options.pb"), "wb") as f:
            f.write(opts.SerializeAsString())

    @staticmethod
    def load_compiled(dirname):
        """Deserialize a save_compiled artifact → CompiledPredictor."""
        return CompiledPredictor(dirname)


class CompiledPredictor:
    """Runs a serialized AOT inference module (no Program needed)."""

    def __init__(self, dirname):
        import json
        import os
        from jax import export as jexport
        with open(os.path.join(dirname, "module.stablehlo"), "rb") as f:
            self._exported = jexport.deserialize(bytearray(f.read()))
        with open(os.path.join(dirname, "signature.json")) as f:
            self.signature = json.load(f)
        pz = np.load(os.path.join(dirname, "params.npz"))
        pdt = self.signature.get("param_dtypes", {})
        self._persist = {}
        for k in pz.files:
            a = pz[k]
            want = pdt.get(k)
            if want and str(a.dtype) != want:
                a = a.view(jnp.dtype(want))  # bf16 stored as uint16
            self._persist[k] = jnp.asarray(a)

    def run(self, feed, return_numpy=True):
        t0 = time.perf_counter()
        with _tm.span("inference.compiled_run", feeds=len(feed)):
            feed_arrays = {
                k: jnp.asarray(np.asarray(v),
                               dtype=self.signature["dtypes"].get(k))
                for k, v in feed.items()}
            outs = self._exported.call(self._persist, feed_arrays)
            if return_numpy:
                outs = [np.asarray(o) for o in outs]
        if _tm.enabled():
            _tm.counter("inference.compiled_requests").inc()
            _tm.histogram("inference.compiled_latency_seconds").observe(
                time.perf_counter() - t0)
        return outs
