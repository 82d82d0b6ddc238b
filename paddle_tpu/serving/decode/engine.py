"""DecodeEngine: the serving face of the incremental decoder.

Wraps `models.transformer.IncrementalDecoder` with everything the
continuous scheduler needs and nothing it doesn't:

- **bucketed prefill**: admitted requests are padded row-wise to a
  fixed bucket set (powers of two up to `num_slots` by default, the
  same discipline as `inference.default_buckets`), so the executable
  count stays `len(prefill_buckets) + 1` — pinned by
  `tpuserve --selftest-decode` and surfaced as the
  `serving.decode.compile_count` gauge;
- **warmup**: every prefill bucket and the step function compile on
  zero feeds at attach time, so live traffic never eats a compile
  stall (the PR 3 warmup story, extended to the decode tier);
- **telemetry**: prefill/step/warmup spans and counters in the
  `serving.decode.*` namespace, flowing into tpustat like every other
  subsystem.

The scheduler talks to this class through a deliberately narrow,
duck-typeable surface (``num_slots / max_new_tokens / init_state /
admit / step / compile_count``) so QoS and slot logic unit-test
against a fake engine in microseconds.
"""
import numpy as np

from ... import telemetry as _tm
from ...inference import default_buckets, next_bucket

__all__ = ["DecodeEngineConfig", "DecodeEngine"]


class DecodeEngineConfig:
    """Knobs for one model's decode tier.

    num_slots: decode batch rows (the KV-cache's slot dimension).
    max_len: decode cache length (generated capacity = max_len - 1);
        defaults to the model config's max_len.
    src_max_len: encoder pad length; defaults to max_len.
    prefill_buckets: admitted-row buckets (default: powers of two up
        to num_slots).
    topk / temperature: in-graph sampling (0 = greedy argmax).
    kv_quant / kv_block: opt-in int8 block-quantized self-attn KV
        cache (None keeps fp32 — the byte-identical default); block
        defaults to the head dim.
    """

    def __init__(self, num_slots=8, max_len=None, src_max_len=None,
                 prefill_buckets=None, topk=0, temperature=1.0,
                 kv_quant=None, kv_block=None):
        self.num_slots = int(num_slots)
        self.max_len = max_len
        self.src_max_len = src_max_len
        self.prefill_buckets = tuple(sorted(
            int(b) for b in (prefill_buckets
                             or default_buckets(self.num_slots))))
        if self.prefill_buckets[-1] < self.num_slots:
            raise ValueError(
                f"largest prefill bucket {self.prefill_buckets[-1]} "
                f"< num_slots {self.num_slots}: a full admission wave "
                f"must fit one prefill")
        self.topk = int(topk)
        self.temperature = float(temperature)
        self.kv_quant = kv_quant
        self.kv_block = kv_block


class DecodeEngine:
    """Compiled continuous-decode executables for one transformer.

    Replica-serving knobs (all default-off, single-engine path
    unchanged): ``device`` pins the decode executables + slot state to
    one device (a farm replica's slice primary); ``prefill_device``
    DISAGGREGATES prefill — a second decoder on a dedicated device
    runs the encoder executables and its KV output is handed
    device-to-device into this engine's slot pool (`jax.device_put`:
    ICI/DMA on TPU, a host copy fallback on CPU), so long-prompt
    prefills stop stalling the token loop's device; ``build_cache``
    shares jit traces across same-config replicas."""

    def __init__(self, model_cfg, params, config=None, device=None,
                 prefill_device=None, build_cache=None):
        from ...models.transformer import IncrementalDecoder
        self.config = config or DecodeEngineConfig()
        self.model_cfg = model_cfg
        self.device = device
        self.decoder = IncrementalDecoder(
            model_cfg, params,
            num_slots=self.config.num_slots,
            max_len=self.config.max_len,
            src_max_len=self.config.src_max_len,
            topk=self.config.topk,
            temperature=self.config.temperature,
            device=device,
            kv_quant=self.config.kv_quant,
            kv_block=self.config.kv_block,
            build_cache=build_cache)
        self.prefill_decoder = None
        if prefill_device is not None:
            # prefill never touches the decode-side KV cache, so the
            # prefill worker stays fp32 regardless of kv_quant; it
            # shares the build cache (prefill keys exclude step-only
            # knobs, so pooled and disaggregated replicas share the
            # same encoder traces)
            self.prefill_decoder = IncrementalDecoder(
                model_cfg, params,
                num_slots=self.config.num_slots,
                max_len=self.config.max_len,
                src_max_len=self.config.src_max_len,
                device=prefill_device,
                build_cache=build_cache)
        if _tm.memledger_enabled():
            self._register_params()

    def _register_params(self, owner=None):
        """Attribute the decoder-held device weight copies. Owner is
        re-stamped at init_state time once the farm has assigned a
        replica index (registration by id moves, never duplicates)."""
        from ...telemetry import memledger as _ml
        if owner is None:
            owner = ("decode" if self.replica_index is None
                     else f"replica{self.replica_index}")
        _ml.register("params", owner, self.decoder.params)
        if self.prefill_decoder is not None:
            _ml.register("params", owner, self.prefill_decoder.params)

    # ----------------------------------------------------- constructors
    @classmethod
    def from_inference_engine(cls, engine, model_cfg, config=None,
                              **kw):
        """Share a served `InferenceEngine`'s parameters (same arrays,
        no copy): the prefill/step executables and the full-program
        predict path serve one checkpoint."""
        return cls(model_cfg, engine.params(), config=config, **kw)

    @classmethod
    def from_scope(cls, scope, model_cfg, config=None, names=None,
                   **kw):
        """Pull parameters out of a training/infer scope by name
        (`names` defaults to every var the scope can produce for the
        decode set — see `models.transformer.decode_params`)."""
        from ...models.transformer import decode_params
        if names is None:
            probe = {}
            for n in _decode_name_universe(model_cfg):
                v = scope.get(n) if hasattr(scope, "get") else None
                if v is not None:
                    probe[n] = np.asarray(v)
            arrays = probe
        else:
            arrays = {n: np.asarray(scope.get(n)) for n in names}
        return cls(model_cfg, decode_params(arrays, model_cfg),
                   config=config, **kw)

    # set by serving.farm at spawn (like ContinuousScheduler's): lands
    # engine-side trace events (prefill, KV handoff) on the right
    # replica pid of a request exemplar; None for single engines
    replica_index = None

    # ------------------------------------------------------- properties
    @property
    def num_slots(self):
        return self.config.num_slots

    @property
    def max_new_tokens(self):
        return self.decoder.max_new_tokens

    @property
    def src_max_len(self):
        return self.decoder.src_max_len

    @property
    def compile_count(self):
        n = self.decoder.compile_count
        if self.prefill_decoder is not None:
            n += self.prefill_decoder.compile_count
        return n

    @property
    def kv_cache_bytes(self):
        """Slot-state footprint (see IncrementalDecoder.kv_cache_bytes)."""
        return self.decoder.kv_cache_bytes()

    # -------------------------------------------------------- lifecycle
    def init_state(self):
        state = self.decoder.init_state()
        if _tm.memledger_enabled():
            # creation site of the KV-cache blocks: owner is the
            # replica (once the farm assigned one), quant rides as
            # metadata so an OOM hint knows fp32 from int8
            from ...telemetry import memledger as _ml
            owner = ("decode" if self.replica_index is None
                     else f"replica{self.replica_index}")
            _ml.register("kv_cache", owner, state,
                         quant=self.config.kv_quant)
            self._register_params(owner)
        return state

    def set_params(self, arrays):
        """Rolling weight update: swap the parameter set under the
        compiled executables (shapes must match -> zero recompile).
        Covers the disaggregated prefill decoder too, atomically from
        the caller's point of view — the replica is drained while this
        runs, so no request sees mixed versions."""
        self.decoder.load_params(arrays)
        if self.prefill_decoder is not None:
            self.prefill_decoder.load_params(arrays)
        if _tm.memledger_enabled():
            self._register_params()

    def warmup(self):
        """Compile every prefill bucket + the step on zero feeds.
        Returns the executable count (== len(prefill_buckets) + 1 when
        this engine built everything itself; shared build caches and
        disaggregation split the count across decoders but the sum is
        pinned at the group level)."""
        pf = self.prefill_decoder or self.decoder
        Ts = self.decoder.src_max_len
        for b in self.config.prefill_buckets:
            with _tm.span("serving.decode.warmup", bucket=b):
                pf.prefill(np.zeros((b, Ts), np.int64),
                           np.ones((b,), np.int64))
            if _tm.enabled():
                _tm.counter("serving.decode.warmup_runs").inc()
        state = self.init_state()
        with _tm.span("serving.decode.warmup", bucket="step"):
            self.decoder.step(state, np.zeros(self.num_slots, np.int64),
                              np.zeros(self.num_slots, np.int64))
        if _tm.enabled():
            _tm.gauge("serving.decode.compile_count").set(
                self.compile_count)
            _tm.gauge("serving.decode.kv_cache_bytes").set(
                self.kv_cache_bytes)
            # kern-registry evidence from the step trace (read via
            # sys.modules: a step that asked for no kernel loaded none)
            import sys
            kr = sys.modules.get("paddle_tpu.ops.kern.registry")
            if kr is not None:
                _tm.gauge("serving.decode.kern_dispatches").set(
                    kr.STATS["dispatches"])
                _tm.gauge("serving.decode.kern_accepted").set(
                    kr.STATS["accepted"])
        return self.compile_count

    # ---------------------------------------------------------- serving
    def admit(self, state, requests, slots):
        """Prefill `requests` (same count as `slots`) and scatter the
        encoder caches into their slot rows. Rows are padded to the
        next prefill bucket so the jit cache sees only bucket shapes.
        With a disaggregated prefill decoder, the encoder runs on its
        dedicated device and the KV output is handed off to the decode
        device before the scatter."""
        n = len(requests)
        Ts = self.decoder.src_max_len
        bucket = next_bucket(n, self.config.prefill_buckets)
        src = np.zeros((bucket, Ts), np.int64)
        src_len = np.ones((bucket,), np.int64)   # pad rows attend pos 0
        for j, r in enumerate(requests):
            s = np.asarray(r.src, np.int64).reshape(-1)
            src[j, :len(s)] = s
            src_len[j] = min(Ts, max(1, int(r.src_len)))
        pf = self.prefill_decoder or self.decoder
        trace = _tm.reqtrace_enabled()
        t0 = _tm.now_us() if trace else 0
        with _tm.span("serving.decode.prefill", rows=n, bucket=bucket):
            out = pf.prefill(src, src_len)
        if trace:
            dur = _tm.now_us() - t0
            for r in requests:
                if r.request_id:
                    _tm.reqtrace.span_at(
                        r.request_id, "engine.prefill", t0, dur,
                        replica=self.replica_index, rows=n,
                        bucket=bucket,
                        disaggregated=self.prefill_decoder is not None)
        if self.prefill_decoder is not None:
            out = self._handoff(out, requests)
        if _tm.enabled():
            _tm.counter("serving.decode.prefill_rows").inc(n)
            _tm.counter("serving.decode.prefill_pad_rows").inc(
                bucket - n)
            _tm.gauge("serving.decode.compile_count").set(
                self.compile_count)
        with _tm.span("serving.decode.write_slots", rows=n):
            return self.decoder.write_slots(state, out, slots)

    def _handoff(self, out, requests=()):
        """Move prefilled KV state (ck, cv, src_bias) from the prefill
        device onto the decode device. `jax.device_put` is the one
        transfer op that lowers to whatever the platform has —
        device-to-device DMA over ICI on TPU, a host round-trip
        fallback on CPU — so the slot scatter always sees colocated
        operands."""
        import jax
        ck, cv, src_bias = out
        nbytes = int(ck.nbytes + cv.nbytes + src_bias.nbytes)
        if _tm.enabled():
            _tm.counter("serving.decode.handoff_bytes").inc(nbytes)
            _tm.counter("serving.decode.handoffs").inc()
        dev = self.device if self.device is not None \
            else jax.devices()[0]
        trace = _tm.reqtrace_enabled()
        t0 = _tm.now_us() if trace else 0
        with _tm.span("serving.decode.handoff"):
            moved = jax.device_put((ck, cv, src_bias), dev)
        if trace:
            dur = _tm.now_us() - t0
            for r in requests:
                if r.request_id:
                    _tm.reqtrace.span_at(
                        r.request_id, "engine.kv_handoff", t0, dur,
                        replica=self.replica_index, bytes=nbytes)
        return moved

    def step(self, state, ids, pos, seed=0):
        """One decode iteration over all slots -> next ids [S]."""
        try:
            nxt = self.decoder.step(state, ids, pos, seed=seed)
        except Exception as e:
            if _tm.memledger_enabled():
                from ...telemetry import memledger as _ml
                _ml.handle_possible_oom(
                    e, context={"site": "decode.step",
                                "replica": self.replica_index})
            raise
        if _tm.enabled():
            _tm.counter("serving.decode.steps").inc()
            _tm.gauge("serving.decode.compile_count").set(
                self.compile_count)
        if _tm.memledger_enabled():
            from ...telemetry import memledger as _ml
            _ml.on_step(context={"site": "decode.step",
                                 "replica": self.replica_index})
        return nxt


def _decode_name_universe(cfg):
    """Every parameter name decode could need, in either checkpoint
    layout (union of unfused + fused names; absent ones just don't
    resolve in the scope)."""
    names = ["src_emb.w_0", "trg_emb.w_0", "proj.w_0"]
    for i in range(cfg.n_layer):
        names += [f"enc{i}_{p}.w_0" for p in "qkvo"]
        names += [f"dec{i}_self_{p}.w_0" for p in "qkvo"]
        names += [f"dec{i}_cross_{p}.w_0" for p in "qkvo"]
        names += [f"enc{i}_qkv.w_0", f"dec{i}_self_qkv.w_0",
                  f"dec{i}_cross_kv.w_0", f"dec{i}_cross_q.w_0"]
        for part in (f"enc{i}_ffn", f"dec{i}_ffn"):
            names += [f"{part}_fc1.w_0", f"{part}_fc1.b_0",
                      f"{part}_fc2.w_0", f"{part}_fc2.b_0"]
    for j in range(5 * cfg.n_layer):
        names += [f"layer_norm_{j}.w_0", f"layer_norm_{j}.b_0"]
    return names
