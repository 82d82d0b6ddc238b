"""Executor: compiles a Program into an XLA module and runs it.

Parity: python/paddle/fluid/executor.py + paddle/fluid/framework/executor.cc.
API-compatible `Executor(place).run(program, feed=..., fetch_list=...)`,
but execution is whole-program: the op list is traced once per
(program-version, feed-signature, fetch-set, mode) into a jitted step
function with persistable buffers DONATED — param/optimizer-state updates
happen in-place in HBM, and one compiled module per step replaces per-op
kernel launches (BASELINE.json north-star).
"""
import logging
import os
import time
import weakref

import numpy as np
import jax
import jax.numpy as jnp

from .framework import default_main_program, Program
from .place import core_place_of
from .scope import global_scope
from .trace import build_step_fn, op_scope
from .dtypes import as_jnp_dtype
from .. import telemetry as _tm
from ..resilience import chaos as _chaos

from .scope import scope_guard  # noqa: F401  (ref executor.py re-exports it)

__all__ = ["Executor", "scope_guard", "as_numpy",
           "resolve_async_steps"]


def resolve_async_steps(arg, attr=None):
    """Async window depth: explicit run(async_steps=) arg > the
    executor attribute > the PADDLE_TPU_ASYNC env var. 0 (the default
    everywhere) is the synchronous path — pinned bit-identical to
    pre-async behavior, without ever importing pipeline_exec."""
    val = arg if arg is not None else attr
    if val is None:
        raw = (os.environ.get("PADDLE_TPU_ASYNC") or "").strip().lower()
        if raw in ("", "0", "off", "false", "none", "no"):
            return 0
        try:
            val = int(raw)
        except ValueError:
            raise ValueError(
                f"PADDLE_TPU_ASYNC={raw!r} is not an integer window "
                "depth")
    k = int(val)
    if k < 0:
        raise ValueError(f"async_steps must be >= 0, got {k}")
    return k

_LOG = logging.getLogger("paddle_tpu.executor")


def as_numpy(tensor):
    """Convert a fetched value (device array / LoDTensor / list of
    either) to numpy (ref executor.py:as_numpy). LoDTensors carrying
    LoD raise, matching the reference's contract — use
    return_numpy=False to get the tensor itself."""
    from ..lod import LoDTensor, LoDTensorArray
    if isinstance(tensor, (list, LoDTensorArray)):
        return [as_numpy(t) for t in tensor]
    if isinstance(tensor, LoDTensor) and tensor.lod():
        raise RuntimeError(
            "Some of your fetched tensors hold LoD information. "
            "They can not be completely cast to Python ndarray. "
            "Please set the parameter 'return_numpy' as 'False' to "
            "return LoDTensor itself directly.")
    return np.asarray(tensor)


def _fetch_var(name, scope=None, return_numpy=True):
    """Fetch a variable's value by name from `scope` (ref
    executor.py:_fetch_var); persistable vars live in the scope used
    with Executor.run."""
    from .scope import global_scope
    assert isinstance(name, str)
    scope = scope if scope is not None else global_scope()
    val = scope.get(name)
    assert val is not None, (
        f"Cannot find {name} in scope. Perhaps you need to make the "
        "variable persistable by using var.persistable = True in your "
        "program.")
    return as_numpy(val) if return_numpy else val


def _count_marked(marks, values):
    """The read-back values of a program's marked variables
    (Program.mark_counter) into the telemetry registry. The mark is the
    gate, as a compile is the compile log's: this does not wait for
    telemetry.enabled()."""
    prefixes = set()
    for (name, (_, kind)), value in zip(marks.items(), values):
        value = np.asarray(value).reshape(-1)[0].item()
        if kind == "gauge":
            _tm.gauge(name).set(value)
        else:
            _tm.counter(name).inc(value)
        prefixes.add(name.split(".")[0])
    for prefix in prefixes:
        _tm.counter(prefix + ".steps").inc()


def _feed_signature(feed):
    return tuple(sorted((k, tuple(np.shape(v)), str(np.asarray(v).dtype) if not hasattr(v, "dtype") else str(v.dtype))
                        for k, v in feed.items()))


class Executor:
    def __init__(self, place=None):
        self.place = core_place_of(place)
        if place is None:
            _LOG.info("Executor(): no place given, running on %r",
                      self.place)
        self._cache = {}
        self._step = 0
        self._seed = 0
        self.check_nan_inf = False   # failure-detection flag (SURVEY §2.8)
        # diagnostics bookkeeping: how many runs took the pre-step state
        # snapshot (must stay 0 with all diag flags off — bench contract)
        self.diag_snapshot_count = 0
        self.last_numerics_report = None
        # stall detection (SURVEY §2.8): a step (excluding its first-run
        # XLA compile) exceeding this wall-clock budget logs a warning —
        # the race/stall analog of the reference's distributed watchdogs.
        self.step_timeout = None     # seconds; None disables
        self.last_step_time = None   # wall seconds of the last run()
        # the most recent recompile explanation (telemetry on only):
        # which ckey component busted the compile cache, per
        # telemetry.attribution.explain_recompile
        self.last_recompile = None
        self._seen_keys = set()
        # per-device on-device step counters (PRNG stream position);
        # donated through every run() so advancing costs no dispatch,
        # with a host-side mirror of the value so diagnostics never
        # need a blocking scalar readback (the counter advances by
        # exactly 1 per run — the mirror is definitionally in sync)
        self._step_counters = {}
        self._step_counter_vals = {}
        # asynchronous step pipeline (tpupipe, core/pipeline_exec.py):
        # run(async_steps=k) / PADDLE_TPU_ASYNC=k defers fetch
        # readback + finite checks behind a k-deep in-flight window.
        # None/0 (the default) is the synchronous path, bit-identical
        # to pre-async behavior — pipeline_exec is only imported once
        # a window is requested (pinned by the bench contract).
        self.async_steps = None
        self._async_pipe = None
        self._prefetchers = {}
        # identity-keyed feed reuse cache: a caller passing the SAME
        # numpy buffer again skips the device re-put entirely (weakly
        # referenced, so it never pins host memory and a recycled id
        # can't alias a dead array). Mutating a previously-fed buffer
        # in place is invisible to it — pass a fresh array, or set
        # feed_cache = False.
        self.feed_cache = True
        self._feed_cache = {}
        # persistable-state donation (default on: params update in
        # place in HBM). donate_state=False trades the in-place update
        # for keeping the previous state alive (async A/B runs).
        # Toggling recompiles (the non-default value joins the ckey).
        self.donate_state = True

    def close(self):
        # abandon any in-flight async steps (call drain() first if the
        # final fetches/checks matter) and stop the prefetch threads
        self.discard_pending()
        for pf in self._prefetchers.values():
            pf.stop()
        self._prefetchers.clear()
        self._cache.clear()
        self._seen_keys.clear()
        self._step_counters.clear()
        self._step_counter_vals.clear()
        self._feed_cache.clear()
        # final flush so a closed executor's run leaves its metrics on
        # record (writes PADDLE_TPU_TELEMETRY_DIR artifacts when set)
        _tm.flush()

    # ------------------------------------------------ async pipeline
    def drain(self):
        """Materialize every in-flight async step (deferred readbacks
        and finite checks run now, in step order — the earliest
        deferred failure raises first). No-op with no window; the
        Guardian calls this before committing a checkpoint."""
        if self._async_pipe is not None:
            self._async_pipe.drain()
        return self

    def discard_pending(self):
        """Abandon in-flight async steps WITHOUT their deferred checks
        (restore/teardown paths — the state is being replaced anyway).
        Returns how many steps were dropped."""
        if self._async_pipe is not None:
            return self._async_pipe.discard()
        return 0

    @property
    def inflight(self):
        """Current async window occupancy (0 when synchronous)."""
        return len(self._async_pipe) if self._async_pipe is not None \
            else 0

    @staticmethod
    def _feed_dtype(program, name):
        """Target numpy dtype for feed `name`, or None when the program
        doesn't declare it (x32 mode downcasts 64-bit like the TPU)."""
        var = program.global_block().vars.get(name)
        dt = as_jnp_dtype(var.dtype) if var is not None else None
        if dt is not None and not jax.config.jax_enable_x64:
            # avoid per-step truncation warnings: TPU runs x32
            dt = {jnp.int64: jnp.int32, jnp.uint64: jnp.uint32,
                  jnp.float64: jnp.float32}.get(dt, dt)
        return np.dtype(dt) if dt is not None else None

    @staticmethod
    def _host_immutable(arr):
        """True when `arr` cannot be mutated through ANY handle: the
        array and its whole base chain are read-only (a read-only view
        over a writeable base is still mutable through the base —
        greedy_decode's in-place token feedback is exactly that kind
        of aliasing hazard)."""
        a = arr
        while a is not None:
            if getattr(getattr(a, "flags", None), "writeable", True):
                return False
            a = a.base if isinstance(a.base, np.ndarray) else None
        return True

    def _put_feeds(self, program, feed, dev, span=None):
        """Feed values → device arrays with ONE transfer each: dtype
        casts happen host-side, and values that are already jax Arrays
        of the right dtype pass through untouched. Numpy feeds are
        reuse-cached by buffer
        identity: the same array object fed again skips the re-put
        (executor.feed_put.reused counts the skips). SAFE by default —
        reuse requires the buffer be genuinely immutable (read-only
        down its base chain, so an in-place mutation is impossible
        rather than merely unexpected); feed_cache="trust" reuses any
        identical buffer for loops that promise not to mutate. `span`
        (executor.feed_put) gets the counts: calls of device_put, their
        bytes, and feeds served from the reuse cache."""
        feed_arrays = {}
        cache = self._feed_cache if self.feed_cache else None
        trust = self.feed_cache == "trust"
        tm_on = _tm.enabled()
        puts = reused = nbytes = 0
        for k, v in feed.items():
            npdt = self._feed_dtype(program, k)
            if isinstance(v, jax.Array) and (npdt is None
                                             or v.dtype == npdt) \
                    and v.sharding.device_set == {dev}:
                feed_arrays[k] = v
                continue
            if cache is not None and isinstance(v, np.ndarray):
                ent = cache.get(k)
                if ent is not None and ent[0]() is v \
                        and ent[1] is dev and ent[2] == npdt \
                        and (trust or self._host_immutable(v)):
                    feed_arrays[k] = ent[3]
                    reused += 1
                    if tm_on:
                        _tm.counter("executor.feed_put.reused").inc()
                    continue
            arr = np.asarray(v)
            if npdt is not None and arr.dtype != npdt:
                arr = arr.astype(npdt)
            feed_arrays[k] = jax.device_put(arr, dev)
            puts += 1
            nbytes += arr.nbytes
            if cache is not None and isinstance(v, np.ndarray):
                cache[k] = (weakref.ref(v), dev, npdt, feed_arrays[k])
        if span is not None:
            span.set(puts=puts, reused=reused, bytes=nbytes)
        return feed_arrays

    def _collect_persist(self, program, scope):
        """Scope values for the program's persistables, with a clear
        error when training state was never initialized."""
        persist = {}
        missing = []
        for v in program.persistable_vars():
            val = scope.get(v.name)
            if val is None:
                missing.append(v.name)
            else:
                persist[v.name] = val
        if missing:
            # vars this program itself produces (startup program case) are fine
            produced = {n for op in program.global_block().ops
                        for n in op.output_names()}
            hard_missing = [n for n in missing if n not in produced]
            if hard_missing:
                raise RuntimeError(
                    f"persistable vars not initialized: {hard_missing[:5]} "
                    f"(+{max(0, len(hard_missing)-5)} more); "
                    "run the startup program first")
        return persist

    @staticmethod
    def _commit(persist, dev):
        """State going into a step that has (committed) feeds, committed
        to `dev` too. The step's outputs come back committed, so state
        that goes in uncommitted — as the startup program leaves it —
        gives the second call a different jit signature, and the whole
        program traces and compiles a second time."""
        return {n: v if getattr(v, "committed", False)
                else jax.device_put(v, dev)
                for n, v in persist.items()}

    @staticmethod
    def _unalias_feeds(feed_arrays, persist):
        """A fed jax.Array that IS a persistable scope buffer would be
        passed both donated (persist) and non-donated (feed) in one jit
        call; donation would invalidate the feed read. Copy such feeds."""
        persist_ids = {id(v) for v in persist.values()}
        for k, v in feed_arrays.items():
            if id(v) in persist_ids:
                feed_arrays[k] = jnp.array(v, copy=True)

    @staticmethod
    def _nonfinite_names(named_values):
        """Names whose (host-read) values contain NaN/Inf. Handles
        bfloat16 etc. (numpy kind 'V': issubdtype(floating) is False
        but np.isfinite works on the ml_dtypes array directly)."""
        bad = []
        for name, val in named_values:
            arr = np.asarray(val)
            if arr.dtype.kind in "fc" or arr.dtype.kind == "V":
                try:
                    ok = bool(np.all(np.isfinite(arr)))
                except TypeError:      # non-float void dtype
                    continue
                if not ok:
                    bad.append(name)
        return bad

    def _check_fetches_finite(self, fetch_names, fetches):
        bad = self._nonfinite_names(zip(fetch_names, fetches))
        if bad:
            raise FloatingPointError(
                f"NaN/Inf detected in fetched var {bad[0]!r}")

    # ------------------------------------------------------------------
    def _check_requested(self, check_nan_inf):
        """Resolve the run(check_nan_inf=...) tri-state: explicit arg >
        the executor attribute > the PADDLE_TPU_CHECK_NAN_INF env
        toggle. Returns "all", "fetches", or False."""
        val = check_nan_inf if check_nan_inf is not None \
            else (self.check_nan_inf or None)
        if val is None:
            from .. import diagnostics as _dg
            if not _dg.check_nan_inf_requested():
                return False
            return _dg.check_mode()
        if not val:
            return False
        return val if val in ("all", "fetches") else "all"

    def _diagnose_nan_inf(self, program, feed_arrays, pre_state,
                          fetch_names, is_test, seed, step_val,
                          detail):
        """A finite check tripped: localize the culprit op by bisection
        and raise NanInfError carrying the NumericsReport (plus a
        flight-recorder dump when the recorder is armed)."""
        from .. import diagnostics as _dg
        if _tm.enabled():
            _tm.counter("diagnostics.nan_inf_count").inc()
        report = None
        if pre_state is not None:
            try:
                report = _dg.localize(
                    program, feed_arrays, pre_state, fetch_names,
                    is_test=is_test, place=self.place, seed=seed,
                    step=step_val)
            except Exception as e:   # diagnosis must not mask the trip
                _LOG.warning("NaN localization failed: %s: %s",
                             type(e).__name__, e)
        if report is None:
            report = _dg.NumericsReport(
                "unknown", step=step_val, seed=seed,
                program_version=program._version,
                detail=detail + "; re-execution did not reproduce a "
                "non-finite value (non-determinism, or the failure "
                "is outside the traced step)")
        else:
            report.detail = (report.detail + "; trigger: " + detail) \
                if report.detail else detail
        self.last_numerics_report = report
        rec = _dg.recorder.active()
        if rec is not None:
            rec.event("nan_inf", step=step_val,
                      op=report.op_type, op_idx=report.op_idx)
            rec.dump(reason="nan_inf", report=report)
        raise _dg.NanInfError(report)

    # ------------------------------------------------------------------
    @staticmethod
    def _validate_requested(validate):
        """Resolve the run(validate=...) tri-state: None defers to the
        PADDLE_TPU_VALIDATE env toggle."""
        if validate is not None:
            return bool(validate)
        return os.environ.get("PADDLE_TPU_VALIDATE", "").lower() \
            not in ("", "0", "false", "off")

    @staticmethod
    def _pre_trace_validate(program, fetch_names, feed_names):
        """Run the static verifier (paddle_tpu/analysis) before tracing;
        error-severity diagnostics raise ProgramVerificationError with
        IR-level locations instead of letting the trace die inside JAX
        with an XLA stack trace."""
        from ..analysis import verify_program
        verify_program(program, fetch_list=fetch_names,
                       feed_names=feed_names, raise_on_error=True)

    def run(self, program=None, feed=None, fetch_list=None, scope=None,
            return_numpy=True, use_program_cache=True, is_test=None,
            validate=None, check_nan_inf=None, async_steps=None):
        program = program if program is not None else default_main_program()
        # the parent of every span below; `compile_run` joins its counts
        # once the compile key is known
        with _tm.span("executor.run", step=self._step,
                      program=program._version) as run_span:
            return self._run(run_span, program, feed, fetch_list, scope,
                             return_numpy, use_program_cache, is_test,
                             validate, check_nan_inf, async_steps)

    def _run(self, run_span, program, feed, fetch_list, scope,
             return_numpy, use_program_cache, is_test, validate,
             check_nan_inf, async_steps):
        k_async = resolve_async_steps(async_steps, self.async_steps)
        scope = scope if scope is not None else global_scope()
        feed = dict(feed or {})
        dev = self.place.jax_device()
        # programs fed by py_reader/open_files queues: pop one batch per
        # step for any reader whose vars aren't explicitly fed (parity:
        # the C++ reader queue; raises core.EOFException when exhausted).
        # In async mode an armed reader (use_double_buffer /
        # layers.double_buffer) is promoted to a DevicePrefetcher: its
        # batches arrive already device_put on a background thread.
        for rd in getattr(program, "_py_readers", []):
            names = [v.name for v in rd.vars]
            if not any(n not in feed for n in names):
                continue
            pf = self._prefetchers.get(id(rd))
            if pf is None and k_async > 0 and rd.is_started() \
                    and getattr(rd, "_device_prefetch", False):
                from .pipeline_exec import DevicePrefetcher
                pf = DevicePrefetcher(
                    rd, dev,
                    lambda name, _p=program: self._feed_dtype(_p, name),
                    capacity=max(2, k_async))
                self._prefetchers[id(rd)] = pf
            if pf is not None:
                try:
                    batch = pf.next_feed()
                except Exception:
                    # EOF or provider error: tear the stage down so a
                    # reset()+start() reader gets a fresh one
                    pf.stop()
                    self._prefetchers.pop(id(rd), None)
                    raise
                for n, v in batch.items():
                    feed.setdefault(n, v)
            elif rd.is_started():
                for n, v in rd.next_feed().items():
                    feed.setdefault(n, v)
        fetch_list = list(fetch_list or [])
        fetch_names = [f.name if hasattr(f, "name") else f for f in fetch_list]
        # values the program marked as counters (Program.mark_counter)
        # ride behind the caller's fetches and are taken off again after
        # the read-back (_finalize_record); no mark, nothing added
        marks = getattr(program, "_device_counters", None)
        n_fetch = len(fetch_names)
        if marks:
            fetch_names = fetch_names + [v for v, _ in marks.values()]
        if is_test is None:
            is_test = getattr(program, "_is_test", False)

        seed = program.random_seed if program.random_seed else self._seed
        self._step += 1
        # chaos: the executor.step injection point (step_fail:at=N
        # raises ChaosFault / SIGKILLs mid-run — the Guardian/auto-
        # resume acid test). One cached-bool check when disarmed.
        if _chaos.armed():
            _chaos.check("executor.step",
                         detail=f"executor step {self._step - 1}",
                         step=self._step - 1)

        # telemetry: one flag check on the disabled path (snapshot must
        # stay empty — pinned by tests/test_bench_contract.py); spans are
        # shared no-op singletons when off
        tm_on = _tm.enabled()
        # diagnostics gates: both resolve to a cached None/False when the
        # env flags are unset — zero extra fetches or device work then
        # (pinned by the bench contract)
        check = self._check_requested(check_nan_inf)
        from ..diagnostics import recorder as _fr
        flight = _fr.active()
        # device-memory ledger: one plain-bool check when off (the
        # module is never imported then — bench-contract pin)
        ml_on = _tm.memledger_enabled()
        t_fp = time.perf_counter() if tm_on else 0.0
        with _tm.span("executor.feed_put", feeds=len(feed),
                      step=self._step - 1) as put_span:
            try:
                feed_arrays = self._put_feeds(program, feed, dev,
                                              put_span)
            except Exception as e:
                if ml_on:
                    from ..telemetry import memledger as _ml
                    _ml.handle_possible_oom(
                        e, context={"site": "executor.feed_put",
                                    "step": self._step - 1,
                                    "program": program._version})
                raise
        if ml_on:
            from ..telemetry import memledger as _ml
            for _n, _v in feed_arrays.items():
                _ml.register("feed", _n, _v)
        if tm_on:
            _tm.histogram("executor.feed_put_seconds").observe(
                time.perf_counter() - t_fp)

        with _tm.span("executor.prepare") as prep_span:
            persist = self._collect_persist(program, scope)
            self._unalias_feeds(feed_arrays, persist)
            if feed_arrays:
                persist = self._commit(persist, dev)
            prep_span.set(persist=len(persist))

            from . import trace as _trace
            ckey = (id(program), program._version,
                    _feed_signature(feed_arrays), tuple(fetch_names),
                    bool(is_test), seed, _trace.FUSE_OPTIMIZER_TAIL,
                    _trace.FUSE_MAX_ELEMS)
            if not self.donate_state:
                # only the non-default mode grows the key — the donating
                # path keeps the historical 8-tuple (bench-contract pin)
                ckey = ckey + ("nodonate",)
            fn = self._cache.get(ckey) if use_program_cache else None
            # first-run (compile) detection must survive
            # use_program_cache=False
            first_run = ckey not in self._seen_keys
            run_span.set(compile_run=first_run)
            if first_run and tm_on and self._seen_keys:
                # a NEW compile key while others are cached: diff it
                # against the nearest seen neighbor and say which
                # component busted the cache (tpuscope recompile
                # explainer)
                from ..telemetry import attribution as _attr
                self.last_recompile = _attr.explain_recompile(
                    "executor", _attr.executor_ckey_fields(ckey),
                    [_attr.executor_ckey_fields(k)
                     for k in self._seen_keys],
                    step=self._step - 1)
            self._seen_keys.add(ckey)

            step_dev = self._step_counters.get(dev)
            if step_dev is None:
                # uncommitted on purpose: a device_put-committed counter
                # would commit every jit OUTPUT (params included) to one
                # device, poisoning later mesh-sharded use of the scope
                # (e.g. startup → PipelineTrainer over a pp mesh)
                step_dev = jnp.asarray(self._step - 1, jnp.int32)
                self._step_counter_vals[dev] = self._step - 1
            if feed_arrays and not step_dev.committed:
                step_dev = jax.device_put(step_dev, dev)   # see _commit
        # what compiles on a key's first run is this executor's: the
        # compile log (telemetry.compile_log) puts it down to the program
        own = _tm.compile_owner(f"executor:{program._version}") \
            if first_run else _tm.compiles.NO_OWNER
        if fn is None:
            if flight is not None:
                flight.event("compile", program=program._version,
                             fetches=len(fetch_names))
            if tm_on:
                _tm.counter("executor.compile_count").inc()
                _tm.gauge("executor.signature_count").set(
                    len(self._seen_keys))
            with own, _tm.span("executor.compile",
                               program=program._version,
                               fetches=len(fetch_names)):
                # opt-in pre-trace verification gate: pay it once per
                # compile (cache hits skip it), catching IR defects
                # before JAX does
                if self._validate_requested(validate):
                    self._pre_trace_validate(program, fetch_names,
                                             list(feed_arrays))
                step_fn = build_step_fn(program, fetch_names, is_test,
                                        self.place)

                # the PRNG key is derived ON DEVICE from a donated step
                # counter rather than host-side fold_in: a host-side
                # jax.random call is an extra dispatch per step
                def stepped(persist, feed, step):
                    with op_scope("rng_key"):
                        key = jax.random.fold_in(
                            jax.random.PRNGKey(seed),
                            step.astype(jnp.uint32))
                    fetches, new_persist = step_fn(persist, feed, key)
                    return fetches, new_persist, step + 1

                fn = jax.jit(stepped,
                             donate_argnums=(0, 2) if self.donate_state
                             else ())
                # the way from a device trace's op to its named_scope
                # (telemetry.compiled_text): shapes only, nothing runs
                _tm.compiles.register_program(
                    f"executor:{program._version}", fn,
                    (persist, feed_arrays, step_dev),
                    program.name_scopes())
                if tm_on:
                    # AOT-compile here (still inside the compile span)
                    # to capture this ckey's FLOPs from cost_analysis
                    # for perf.mfu — the executable replaces the jit
                    # wrapper, so the capture costs no second compile
                    from ..telemetry import attribution as _attr
                    fn = _attr.instrument_compile(
                        fn, (persist, feed_arrays, step_dev), ckey,
                        feed_arrays, kind="executor")
            if use_program_cache:
                self._cache[ckey] = fn
        elif tm_on:
            _tm.counter("executor.cache_hit_count").inc()

        # the host mirror tracks the donated counter (+1 per run), so
        # diagnostics step attribution never needs a blocking readback
        # of a counter an in-flight step hasn't produced yet
        step_val = self._step_counter_vals.get(dev, self._step - 1)
        pre_state = None
        if check:
            # host snapshot of the donated state so a trip can
            # re-execute this exact step eagerly (np.array copy:
            # np.asarray may alias a CPU buffer that donation is about
            # to invalidate). In async mode EVERY in-flight step holds
            # its own snapshot — the deferred check of step N bisects
            # against step N's state, not the newest.
            pre_state = {name: np.array(v, copy=True)
                         for name, v in persist.items()}
            self.diag_snapshot_count += 1
        t0 = time.perf_counter()
        try:
            with own, _tm.span("executor.step", step=self._step - 1,
                               compile_run=first_run):
                fetches, new_persist, step_dev = fn(persist, feed_arrays,
                                                    step_dev)
        except Exception as e:
            # the counter was donated into the failed execution — drop
            # it so the next run() re-seeds instead of passing a deleted
            # buffer forever
            self._step_counters.pop(dev, None)
            self._step_counter_vals.pop(dev, None)
            if ml_on:
                # RESOURCE_EXHAUSTED anywhere in the step turns into a
                # typed MemoryReport through the flight recorder; any
                # other exception passes through untouched
                from ..telemetry import memledger as _ml
                _ml.handle_possible_oom(
                    e, context={"site": "executor.step",
                                "step": self._step - 1,
                                "program": program._version})
            raise
        self._step_counters[dev] = step_dev
        self._step_counter_vals[dev] = step_val + 1
        if self.step_timeout is not None:
            # completion barrier only when the watchdog is armed — don't
            # break async dispatch for return_numpy=False callers
            jax.block_until_ready(fetches)
        dt = time.perf_counter() - t0
        self.last_step_time = dt
        hbm = None
        if ml_on:
            # the step's outputs are the creation site of the next
            # step's state: attribute params vs optimizer slots vs
            # gradsync EF by name, then take the cheap per-step sample
            # (peaks, timeline, over-cap watch)
            from ..telemetry import memledger as _ml
            for _n, _v in new_persist.items():
                _ml.register(_ml.classify_persist_name(_n), _n, _v)
            hbm = _ml.on_step(step=self._step - 1,
                              context={"site": "executor.step",
                                       "step": self._step - 1,
                                       "program": program._version})
        if flight is not None:
            # the ring carries the per-step HBM watermark so an OOM
            # post-mortem shows the memory trajectory, not one number
            if hbm is not None:
                flight.record(step=self._step - 1,
                              program=program._version,
                              compile=first_run, step_s=round(dt, 5),
                              fetches=len(fetch_names), hbm=hbm)
            else:
                flight.record(step=self._step - 1,
                              program=program._version,
                              compile=first_run, step_s=round(dt, 5),
                              fetches=len(fetch_names))
        if tm_on:
            _tm.counter("executor.steps").inc()
            _tm.histogram("executor.step_seconds").observe(dt)
            # attribution window: fold this step's FLOPs/examples into
            # the perf.mfu / perf.goodput.* gauges (compile runs only
            # re-anchor the window — compile time is not throughput)
            from ..telemetry import attribution as _attr
            _attr.on_step(ckey, dt, compile_run=first_run,
                          feed_arrays=feed_arrays)
            # watermark gauges; a no-op on backends without allocator
            # stats (capability probed once — see telemetry.memory)
            _tm.sample_device_memory()
            # fleet spool heartbeat: a no-op until a rank is configured
            # (fleet.init / PADDLE_TPU_FLEET_RANK); with a spool dir it
            # periodically flushes this rank's snapshot for the
            # coordinator-side FleetCollector merge. Deferred to
            # materialization in async mode (the heartbeat should
            # attest a COMPLETED step, not a queued one).
            if k_async == 0:
                _tm.fleet.on_step(dt)
        if (self.step_timeout is not None and not first_run
                and dt > self.step_timeout):
            if tm_on:
                _tm.counter("executor.stall_warnings").inc()
            _LOG.warning(
                "executor stall: step %d took %.2fs (timeout %.2fs) — "
                "program version %s, %d feeds", self._step - 1, dt,
                self.step_timeout, program._version, len(feed_arrays))
        if k_async > 0:
            # XLA may alias a fetch that is ALSO a persistable output
            # onto the persist buffer; the next queued step donates
            # that buffer, which would invalidate the still-pending
            # fetch — copy such fetches to their own buffer (async
            # only: the sync path reads them back before any donation)
            fetches = [jnp.array(f, copy=True) if n in new_persist
                       else f
                       for n, f in zip(fetch_names, fetches)]
        with _tm.span("executor.scope_write", persist=len(new_persist)):
            for name, val in new_persist.items():
                scope.set(name, val)

        rec = {
            "step": self._step - 1, "step_val": step_val,
            "fetches": fetches, "fetch_names": fetch_names,
            "new_persist": new_persist, "program": program,
            "feed_arrays": feed_arrays, "pre_state": pre_state,
            "check": check, "is_test": bool(is_test), "seed": seed,
            "return_numpy": return_numpy, "flight": flight,
            "tm_on": tm_on, "dt": dt, "deferred": k_async > 0,
            "marks": marks, "n_fetch": n_fetch,
        }
        if k_async > 0:
            from .pipeline_exec import PendingStep, StepWindow
            if tm_on:
                _tm.counter("executor.async_steps").inc()
            pipe = self._async_pipe
            if pipe is None:
                pipe = self._async_pipe = StepWindow(k_async)
            pipe.depth = max(1, k_async)
            # push applies backpressure: a full window materializes its
            # oldest step first (deferred checks may raise HERE, for
            # that older step)
            return pipe.push(PendingStep(pipe, rec,
                                         self._finalize_record))
        out = self._finalize_record(rec)
        # the handles of the state that went into the step (donated, or
        # replaced in the scope) die with this frame; dropping them here
        # gives that stretch, which follows the read-back, a name
        with _tm.span("executor.release", handles=len(persist)):
            del persist, rec
        return out

    def _finalize_record(self, rec):
        """Post-step work — finite checks, NaN diagnosis, numpy
        readback, flight-recorder loss annotation. Runs inline on the
        synchronous path; a deferred (async) step runs it at
        materialization time against its OWN record, so errors and
        telemetry attribute to the step that produced them."""
        fetches = rec["fetches"]
        fetch_names = rec["fetch_names"]
        check = rec["check"]
        tm_on = rec["tm_on"]
        flight = rec["flight"]
        if rec["deferred"]:
            t_w = time.perf_counter()
            with _tm.span("executor.pending_wait", step=rec["step"]):
                jax.block_until_ready(fetches)
            if tm_on:
                _tm.histogram("executor.pending_wait_seconds").observe(
                    time.perf_counter() - t_w)
                _tm.fleet.on_step(rec["dt"])

        if check and (fetches or check == "all"):
            t_fc = time.perf_counter()
            with _tm.span("executor.finite_check", step=rec["step"]):
                bad = self._nonfinite_names(zip(fetch_names, fetches))
                where = "fetched vars"
                if not bad and check == "all":
                    # the reference's FLAGS_check_nan_inf checks every
                    # op output; the whole-program analog is the full
                    # updated state (params + optimizer accumulators)
                    bad = self._nonfinite_names(
                        rec["new_persist"].items())
                    where = "updated persistable state"
            if tm_on:
                _tm.histogram("executor.finite_check_seconds").observe(
                    time.perf_counter() - t_fc)
            if bad:
                detail = (f"non-finite {where}: "
                          f"{bad[:4]}{'...' if len(bad) > 4 else ''}")
                if rec["deferred"]:
                    detail += (f" (deferred check of step "
                               f"{rec['step_val']}, materialized "
                               f"behind the async window)")
                self._diagnose_nan_inf(
                    rec["program"], rec["feed_arrays"],
                    rec["pre_state"], fetch_names, rec["is_test"],
                    rec["seed"], rec["step_val"], detail=detail)

        marked = ()
        if rec["marks"]:
            n = rec["n_fetch"]
            fetches, marked = fetches[:n], fetches[n:]
        if rec["return_numpy"] or marked:
            t_rb = time.perf_counter()
            with _tm.span("executor.fetch_readback",
                          n=len(fetches) + len(marked), step=rec["step"]):
                if rec["return_numpy"]:
                    fetches = [np.asarray(f) for f in fetches]
                marked = [np.asarray(f) for f in marked]
            if tm_on:
                _tm.histogram("executor.fetch_readback_seconds").observe(
                    time.perf_counter() - t_rb)
            if marked:
                _count_marked(rec["marks"], marked)
            if rec["return_numpy"] and flight is not None and fetches \
                    and getattr(fetches[0], "size", 0) == 1 \
                    and np.asarray(fetches[0]).dtype.kind in "fV":
                flight.annotate(
                    loss=float(np.asarray(fetches[0]).astype(
                        np.float32).ravel()[0]))
        return fetches

    def _scan_oom_hook(self, e, steps):
        """Memledger OOM classification for the scanned-window path;
        never raises (the original exception propagates)."""
        if _tm.memledger_enabled():
            from ..telemetry import memledger as _ml
            _ml.handle_possible_oom(
                e, context={"site": "executor.run_scanned",
                            "steps": steps})

    # ------------------------------------------------------------------
    def run_scanned(self, program=None, feed=None, fetch_list=None,
                    scope=None, return_numpy=True, is_test=None,
                    steps=None):
        """Run `steps` training steps as ONE compiled XLA program
        (lax.scan over the step function, feeds stacked on a leading
        [steps] axis). Returns stacked fetches [steps, ...].

        This is the TPU-native replacement for the reference's hot
        host-side train loop (python/paddle/fluid/trainer.py:train /
        async_executor.cc): instead of one host→device dispatch per
        batch, the whole window runs on-device — dispatch latency is
        paid once per window instead of once per step.

        Each step gets its own fold_in key, so
        dropout streams match `steps` sequential run() calls in
        distribution (not bit-for-bit: run() folds the executor's global
        step counter, the scan folds the window-local index)."""
        program = program if program is not None else default_main_program()
        scope = scope if scope is not None else global_scope()
        feed = dict(feed or {})
        fetch_list = list(fetch_list or [])
        fetch_names = [f.name if hasattr(f, "name") else f
                       for f in fetch_list]
        if is_test is None:
            is_test = getattr(program, "_is_test", False)

        lens = {k: np.shape(v)[0] for k, v in feed.items()}
        if steps is None:
            if not lens:
                raise ValueError("run_scanned needs feeds (leading axis = "
                                 "steps) or an explicit steps=")
            steps = next(iter(lens.values()))
        bad = {k: n for k, n in lens.items() if n != steps}
        if bad:
            raise ValueError(
                f"feeds must have leading steps axis {steps}; got {bad}")

        seed = program.random_seed if program.random_seed else self._seed
        key = jax.random.fold_in(jax.random.PRNGKey(seed), self._step)
        self._step += steps

        dev = self.place.jax_device()
        feed_arrays = self._put_feeds(program, feed, dev)

        persist = self._collect_persist(program, scope)
        self._unalias_feeds(feed_arrays, persist)
        persist = self._commit(persist, dev)

        # run() derives its PRNG stream from a donated on-device counter;
        # this window advances self._step without touching it, so drop
        # the counter up front (exception-safe) and let the next run()
        # re-seed from self._step
        self._step_counters.pop(dev, None)
        self._step_counter_vals.pop(dev, None)

        if _tm.enabled():
            _tm.counter("executor.scan_windows").inc()
            _tm.counter("executor.scan_steps").inc(steps)
        from . import trace as _trace
        ckey = ("scan", steps, id(program), program._version,
                _feed_signature(feed_arrays), tuple(fetch_names),
                bool(is_test), _trace.FUSE_OPTIMIZER_TAIL,
                _trace.FUSE_MAX_ELEMS)
        fn = self._cache.get(ckey)
        if fn is None:
            step_fn = build_step_fn(program, fetch_names, is_test,
                                    self.place)

            def scanned(persist, feeds, key):
                keys = jax.random.split(key, steps)

                def body(carry, xs):
                    feed_t, k = xs
                    fetches, new_carry = step_fn(carry, feed_t, k)
                    return new_carry, fetches

                new_persist, fetches = jax.lax.scan(
                    body, persist, (feeds, keys))
                return fetches, new_persist

            fn = jax.jit(scanned, donate_argnums=(0,))
            self._cache[ckey] = fn

        with _tm.span("executor.scan_window", steps=steps):
            try:
                fetches, new_persist = fn(persist, feed_arrays, key)
            except Exception as e:
                self._scan_oom_hook(e, steps)
                raise
        for name, val in new_persist.items():
            scope.set(name, val)
        if _tm.memledger_enabled():
            # a scanned window multiplies live staging by K (ROADMAP
            # item 2) — one ledger sample per window keeps the
            # trajectory visible without per-iteration host work
            from ..telemetry import memledger as _ml
            for _n, _v in new_persist.items():
                _ml.register(_ml.classify_persist_name(_n), _n, _v)
            _ml.register("staging", "scan_window", fetches)
            _ml.on_step(step=self._step - 1,
                        context={"site": "executor.run_scanned",
                                 "steps": steps})
        if self.check_nan_inf and fetches:
            try:
                self._check_fetches_finite(fetch_names, fetches)
            except FloatingPointError as e:
                # scanned windows donate state per window, not per
                # step — no pre-step snapshot exists to bisect against
                raise FloatingPointError(
                    f"{e} (in a {steps}-step scanned window; replay "
                    "the window with per-step Executor.run("
                    "check_nan_inf=True) to localize the culprit op)"
                ) from None
        if return_numpy:
            return [np.asarray(f) for f in fetches]
        return fetches

    # convenience used by tests/tools
    def run_startup(self, startup_program=None, scope=None):
        from .framework import default_startup_program
        return self.run(startup_program or default_startup_program(),
                        feed={}, fetch_list=[], scope=scope)
