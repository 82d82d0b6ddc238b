"""Transformer-base NMT (ref benchmark/fluid/models/machine_translation.py
+ the fluid book transformer: encoder-decoder, multi-head attention,
label smoothing, noam LR).

TPU-native notes: padded [B,T] batches + in-graph attention biases from
sequence lengths (replacing LoD), flash-attention Pallas kernel on the
hot path, bf16-ready (normalizations compute in fp32).
"""
import numpy as np

from .. import layers
from .. import telemetry as _tm

__all__ = ["transformer", "build_program", "build_infer_program",
           "greedy_decode", "convert_qkv_checkpoint",
           "decode_params", "IncrementalDecoder",
           "TransformerConfig"]


class TransformerConfig:
    def __init__(self, src_vocab=10000, trg_vocab=10000, max_len=256,
                 d_model=512, d_inner=2048, n_head=8, n_layer=6,
                 dropout=0.1, label_smooth_eps=0.1, fused_qkv=False):
        self.src_vocab = src_vocab
        self.trg_vocab = trg_vocab
        self.max_len = max_len
        self.d_model = d_model
        self.d_inner = d_inner
        self.n_head = n_head
        self.n_layer = n_layer
        self.dropout = dropout
        self.label_smooth_eps = label_smooth_eps
        # one [d, 3HDh] qkv matmul (MXU tiling) — OPT-IN: the default
        # False keeps the reference's per-projection weight names, so
        # checkpoints from prior builds / converted reference models
        # load unchanged; the perf paths (bench.py, chip_smoke.py)
        # pass fused_qkv=True explicitly
        self.fused_qkv = fused_qkv

    @staticmethod
    def base():
        return TransformerConfig()

    @staticmethod
    def tiny():
        return TransformerConfig(src_vocab=128, trg_vocab=128, max_len=32,
                                 d_model=64, d_inner=128, n_head=4,
                                 n_layer=2, dropout=0.0)


def _pad_bias(seq_len, maxlen):
    """[B] lengths -> additive attention bias [B,1,1,T] (0 keep / -1e9 pad)."""
    mask = layers.sequence_mask(seq_len, maxlen=maxlen, dtype="float32")
    bias = layers.scale(mask, scale=1e9, bias=-1e9)   # 1->0, 0->-1e9
    return layers.unsqueeze(bias, [1, 2])


def _embed(ids, vocab, cfg, name):
    emb = layers.embedding(ids, size=[vocab, cfg.d_model], name=name)
    emb = layers.scale(emb, scale=float(np.sqrt(cfg.d_model)))
    emb = layers.add_position_encoding(emb)
    if cfg.dropout:
        emb = layers.dropout(emb, cfg.dropout,
                             dropout_implementation="upscale_in_train")
    return emb


def _ffn(x, cfg, name):
    h = layers.fc(x, cfg.d_inner, num_flatten_dims=2, act="relu",
                  name=f"{name}_fc1")
    if cfg.dropout:
        h = layers.dropout(h, cfg.dropout,
                           dropout_implementation="upscale_in_train")
    return layers.fc(h, cfg.d_model, num_flatten_dims=2, name=f"{name}_fc2")


def _res_norm(x, residual, cfg):
    out = layers.elementwise_add(x, residual)
    return layers.layer_norm(out, begin_norm_axis=2)


def encoder(src_emb, src_bias, cfg):
    x = src_emb
    for i in range(cfg.n_layer):
        attn = layers.multi_head_attention(
            x, x, x, attn_bias=src_bias,
            d_key=cfg.d_model // cfg.n_head,
            d_value=cfg.d_model // cfg.n_head,
            d_model=cfg.d_model, n_head=cfg.n_head,
            dropout_rate=cfg.dropout, name=f"enc{i}",
            fused_qkv=cfg.fused_qkv)
        x = _res_norm(attn, x, cfg)
        ff = _ffn(x, cfg, f"enc{i}_ffn")
        x = _res_norm(ff, x, cfg)
    return x


def decoder(trg_emb, enc_out, trg_bias, src_bias, cfg):
    x = trg_emb
    for i in range(cfg.n_layer):
        self_attn = layers.multi_head_attention(
            x, x, x, attn_bias=trg_bias, causal=True,
            d_key=cfg.d_model // cfg.n_head,
            d_value=cfg.d_model // cfg.n_head,
            d_model=cfg.d_model, n_head=cfg.n_head,
            dropout_rate=cfg.dropout, name=f"dec{i}_self",
            fused_qkv=cfg.fused_qkv)
        x = _res_norm(self_attn, x, cfg)
        cross = layers.multi_head_attention(
            x, enc_out, enc_out, attn_bias=src_bias,
            d_key=cfg.d_model // cfg.n_head,
            d_value=cfg.d_model // cfg.n_head,
            d_model=cfg.d_model, n_head=cfg.n_head,
            dropout_rate=cfg.dropout, name=f"dec{i}_cross",
            fused_qkv=cfg.fused_qkv)
        x = _res_norm(cross, x, cfg)
        ff = _ffn(x, cfg, f"dec{i}_ffn")
        x = _res_norm(ff, x, cfg)
    return x


def transformer(src, src_len, trg, trg_len, cfg):
    """Returns per-position logits [B, T_trg, trg_vocab]."""
    T_src = int(src.shape[1])
    T_trg = int(trg.shape[1])
    src_bias = _pad_bias(src_len, T_src)
    trg_bias = _pad_bias(trg_len, T_trg)
    enc_in = _embed(src, cfg.src_vocab, cfg, "src_emb")
    enc_out = encoder(enc_in, src_bias, cfg)
    dec_in = _embed(trg, cfg.trg_vocab, cfg, "trg_emb")
    dec_out = decoder(dec_in, enc_out, trg_bias, src_bias, cfg)
    return layers.fc(dec_out, cfg.trg_vocab, num_flatten_dims=2,
                     bias_attr=False, name="proj")


def build_program(cfg=None, maxlen=None, use_noam=True, warmup=4000,
                  lr=2.0):
    """Declares feeds (src, src_len, trg, trg_len, label) and returns
    (feeds, avg_cost, token_count)."""
    cfg = cfg or TransformerConfig.base()
    T = maxlen or cfg.max_len
    src = layers.data("src", shape=[T], dtype="int64")
    src_len = layers.data("src_len", shape=[], dtype="int64",
                          append_batch_size=True)
    trg = layers.data("trg", shape=[T], dtype="int64")
    trg_len = layers.data("trg_len", shape=[], dtype="int64",
                          append_batch_size=True)
    label = layers.data("label", shape=[T], dtype="int64")

    logits = transformer(src, src_len, trg, trg_len, cfg)

    lab3 = layers.unsqueeze(label, [2])
    # fused smoothed CE: identical numerics to the reference's
    # one_hot→label_smooth→soft-label CE composition, but never
    # materializes the [B,T,V] target tensors (see kernels_nn._softmax_ce)
    loss = layers.softmax_with_cross_entropy(
        logits, lab3, smooth_epsilon=cfg.label_smooth_eps or 0.0)

    # mask padded target positions; normalize by real token count
    tmask = layers.sequence_mask(trg_len, maxlen=T, dtype="float32")
    loss = layers.squeeze(loss, [2]) if len(loss.shape) == 3 else loss
    masked = layers.elementwise_mul(loss, tmask)
    token_count = layers.reduce_sum(tmask)
    avg_cost = layers.elementwise_div(layers.reduce_sum(masked),
                                      layers.elementwise_max(
                                          token_count,
                                          layers.fill_constant([], "float32", 1.0)))
    feeds = [src, src_len, trg, trg_len, label]
    return feeds, avg_cost, token_count


def build_infer_program(cfg=None, maxlen=None):
    """Inference graph (no labels/loss): (feeds, logits [B,T,V]).

    Same parameter names as build_program (build under a fresh
    unique_name.guard in a fresh program so the trained scope binds),
    the book's machine_translation inference surface."""
    cfg = cfg or TransformerConfig.base()
    T = maxlen or cfg.max_len
    src = layers.data("src", shape=[T], dtype="int64")
    src_len = layers.data("src_len", shape=[], dtype="int64",
                          append_batch_size=True)
    trg = layers.data("trg", shape=[T], dtype="int64")
    trg_len = layers.data("trg_len", shape=[], dtype="int64",
                          append_batch_size=True)
    logits = transformer(src, src_len, trg, trg_len, cfg)
    return ["src", "src_len", "trg", "trg_len"], logits


def greedy_decode(exe, infer_program, logits_var, src, src_len, bos=0,
                  eos=None, fetch_argmax=False):
    """Autoregressive greedy decode through the compiled inference
    program: ONE executable (static [B, T] shapes) run T-1 times, the
    argmax at step t-1 fed back as token t. Returns ids [B, T]
    (position 0 is `bos`). Stops early when every row has emitted
    `eos` (the emitted tail after eos is garbage by construction —
    mask on eos downstream, like the reference's post-processing).

    T comes from src.shape[1] and must equal the maxlen the infer
    program was built with (the graph bakes it into the attention
    bias shapes).

    fetch_argmax=True appends an in-graph arg_max over the vocab axis
    (once per program; cached on the program object) and fetches the
    [B, T] token ids instead of the [B, T, V] logits — O(T) host
    readback per step instead of O(T*V). The default keeps the raw
    logits so the helper stays usable for sampling/beam scoring
    experiments at tiny configs; production decode wants the argmax
    fetch (or the KV-cached `IncrementalDecoder`, which never re-runs
    the prefix at all)."""
    T = int(src.shape[1])
    B = src.shape[0]
    pvars = infer_program.global_block().vars
    built_T = int(pvars["trg"].shape[-1])
    if built_T != T:
        raise ValueError(
            f"src length {T} != infer program's built length "
            f"{built_T}; rebuild build_infer_program(maxlen={T})")
    fetch_var = logits_var
    if fetch_argmax:
        fetch_var = getattr(infer_program, "_greedy_argmax_var", None)
        if fetch_var is None:
            from ..core import framework as _fw
            with _fw.program_guard(infer_program):
                fetch_var = layers.argmax(logits_var, axis=-1)
            infer_program._greedy_argmax_var = fetch_var
    ids = np.zeros((B, T), dtype=np.int64)
    ids[:, 0] = bos
    done = np.zeros((B,), bool)
    for t in range(1, T):
        out = exe.run(infer_program,
                      feed={"src": src, "src_len": src_len,
                            "trg": ids,
                            "trg_len": np.full((B,), t, np.int64)},
                      fetch_list=[fetch_var], is_test=True)
        if fetch_argmax:
            nxt = np.asarray(out[0])[:, t - 1]        # [B] ids
        else:
            step = np.asarray(out[0])[:, t - 1, :]    # [B, V]
            nxt = step.argmax(-1)
        ids[:, t] = nxt
        if eos is not None:
            done |= nxt == eos
            if done.all():
                break
    return ids


def convert_qkv_checkpoint(arrays, cfg, to_fused):
    """Convert a parameter dict between the UNFUSED (per-projection
    enc{i}_q.w_0 / _k / _v — the reference layout and this model's
    default) and FUSED (enc{i}_qkv.w_0, dec{i}_cross_kv.w_0 — the perf
    layout bench.py opts into) checkpoint layouts, in either
    direction. Returns a new dict; non-attention entries pass through
    unchanged. Fusion order matches multi_head_attention's split:
    [q | k | v] (or [k | v]) along the output axis."""
    out = dict(arrays)

    def fuse(base, parts, fused_name):
        names = [f"{base}_{p}.w_0" for p in parts]
        if not all(n in out for n in names):
            return
        ws = [out.pop(n) for n in names]
        out[fused_name] = np.concatenate(ws, axis=1)

    def split(base, parts, fused_name):
        if fused_name not in out:
            return
        w = out.pop(fused_name)
        pieces = np.split(w, len(parts), axis=1)
        for p, piece in zip(parts, pieces):
            out[f"{base}_{p}.w_0"] = piece

    op = fuse if to_fused else split
    for i in range(cfg.n_layer):
        op(f"enc{i}", ("q", "k", "v"), f"enc{i}_qkv.w_0")
        op(f"dec{i}_self", ("q", "k", "v"), f"dec{i}_self_qkv.w_0")
        op(f"dec{i}_cross", ("k", "v"), f"dec{i}_cross_kv.w_0")
    return out


# ---------------------------------------------------------------------------
# incremental (KV-cached) decode — the tpudecode serving tier
# ---------------------------------------------------------------------------
def _ln_index(cfg, part, layer, sub):
    """Deterministic layer_norm parameter index. transformer() builds
    norms in a fixed order under a fresh unique_name.guard: encoder
    layer i contributes layer_norm_{2i} (attn) and _{2i+1} (ffn);
    decoder layer i contributes _{2L+3i} (self), +1 (cross), +2 (ffn).
    Pinned by decode_params' existence check against the scope."""
    L = cfg.n_layer
    if part == "enc":
        return 2 * layer + {"attn": 0, "ffn": 1}[sub]
    return 2 * L + 3 * layer + {"self": 0, "cross": 1, "ffn": 2}[sub]


def decode_params(arrays, cfg):
    """Validate + normalize a transformer parameter dict for
    incremental decode. Accepts BOTH checkpoint layouts: the unfused
    per-projection default and the fused qkv/kv perf layout (detected
    by its `*_qkv.w_0` names and split back via
    `convert_qkv_checkpoint`). Returns a new {name: array} dict
    restricted to the decode-relevant parameters; raises KeyError
    naming every missing parameter on a mismatch."""
    arrays = dict(arrays)
    if any(k.endswith("_qkv.w_0") or k.endswith("_kv.w_0")
           for k in arrays):
        arrays = convert_qkv_checkpoint(arrays, cfg, to_fused=False)
    need = ["src_emb.w_0", "trg_emb.w_0", "proj.w_0"]
    for i in range(cfg.n_layer):
        need += [f"enc{i}_{p}.w_0" for p in "qkvo"]
        need += [f"dec{i}_self_{p}.w_0" for p in "qkvo"]
        need += [f"dec{i}_cross_{p}.w_0" for p in "qkvo"]
        for part in (f"enc{i}_ffn", f"dec{i}_ffn"):
            need += [f"{part}_fc1.w_0", f"{part}_fc1.b_0",
                     f"{part}_fc2.w_0", f"{part}_fc2.b_0"]
    for j in range(5 * cfg.n_layer):        # 2L encoder + 3L decoder
        need += [f"layer_norm_{j}.w_0", f"layer_norm_{j}.b_0"]
    missing = sorted(n for n in need if n not in arrays)
    if missing:
        raise KeyError(
            f"decode_params: {len(missing)} transformer parameters "
            f"missing (config mismatch or foreign checkpoint?): "
            f"{missing[:8]}{'...' if len(missing) > 8 else ''}")
    return {n: arrays[n] for n in need}


class IncrementalDecoder:
    """KV-cached single-token transformer decode over a fixed slot
    pool — the compute core of `paddle_tpu.serving.decode`.

    Instead of re-running the whole [B, T] inference program once per
    token (greedy_decode: O(T^2) compute, O(T*V) readback per step),
    this holds a static-shape cache `[n_layer, num_slots, max_len,
    n_head, d_head]` and compiles exactly TWO kinds of executables:

    - ``prefill(src, src_len)`` (one per row bucket): encoder forward
      plus the per-layer cross-attention K/V projections of enc_out —
      everything decode steps need; enc_out itself never persists.
    - ``step(ids, pos)`` (exactly one): embed the current token per
      slot, scatter its self-attention K/V into the cache at `pos`,
      attend over positions <= pos, and return the next token id per
      slot via IN-GRAPH argmax (or top-k sampling) — only
      ``[num_slots]`` int32 ids cross the host boundary per token.

    Slots are independent rows: every op is row-wise in the slot dim,
    so a slot's token stream is unaffected by who else occupies the
    batch — continuous (iteration-level) batching is token-identical
    to one-at-a-time greedy_decode. The math mirrors the traced
    program's kernels exactly (same einsums, f32 `_attn_softmax`,
    f32 layer-norm internals), keeping argmax parity.

    Parameters come from `decode_params` (both `convert_qkv_checkpoint`
    layouts accepted). Sampling: ``topk=0`` (default) is greedy argmax;
    ``topk=k`` draws from the top-k logits at ``temperature`` using the
    per-step ``seed`` fed to `step` (in-graph, still one executable).

    Replica-serving extensions (all default-off; the single-engine
    path is byte-identical without them — pinned by the bench
    contract):

    - ``device``: pin params + slot state to one jax device. The
      jitted functions follow their committed inputs, so N decoders
      on N devices share *traces* but get per-device executables —
      how `serving.farm` places replicas on disjoint mesh slices.
    - ``kv_quant="int8"``: store the self-attn caches as int8 codes +
      fp32 absmax scales over ``kv_block``-wide blocks of the head
      dim (gradsync's wire format, imported lazily so the fp32 path
      never loads it), dequantized in-graph at attention time.
      Cross-attn caches stay fp32 (written once per request, read
      every step — quantizing them buys little and costs parity).
    - ``build_cache``: an object with ``get_or_build(key, build) ->
      (fn, built)`` (e.g. `serving.farm.SharedBuildCache`) shared by
      same-config replicas so each (bucket, step) traces once per
      group; `compile_count` then counts only the builds THIS decoder
      performed.
    - ``return_logits``: the step also returns the pre-sampling
      [S, V] logits, stashed on ``last_logits`` — parity tests report
      max logit deltas without a second executable shape.
    """

    def __init__(self, cfg, params, num_slots, max_len=None,
                 src_max_len=None, topk=0, temperature=1.0,
                 device=None, kv_quant=None, kv_block=None,
                 build_cache=None, return_logits=False):
        self.cfg = cfg
        self.num_slots = int(num_slots)
        self.max_len = int(max_len or cfg.max_len)
        self.src_max_len = int(src_max_len or self.max_len)
        if self.num_slots < 1 or self.max_len < 2:
            raise ValueError("need num_slots >= 1 and max_len >= 2")
        self.topk = int(topk)
        self.temperature = float(temperature)
        self.device = device
        if kv_quant in ("", "fp32", "none"):
            kv_quant = None
        if kv_quant not in (None, "int8"):
            raise ValueError(f"kv_quant={kv_quant!r} not in "
                             f"(None, 'int8')")
        self.kv_quant = kv_quant
        Dh = cfg.d_model // cfg.n_head
        self.kv_block = int(kv_block or Dh)
        if self.kv_quant and (self.kv_block < 1
                              or Dh % self.kv_block != 0):
            raise ValueError(
                f"kv_block={self.kv_block} must divide the head dim "
                f"{Dh} so scales broadcast over whole blocks")
        self.return_logits = bool(return_logits)
        self.last_logits = None         # [S, V] after step() when opted in
        self._build_cache = build_cache
        self.params = {k: self._put(v)
                       for k, v in decode_params(params, cfg).items()}
        self._prefill_jit = {}          # rows -> jitted prefill
        self._step_jit = None
        self.compile_count = 0          # executables built (pinned)

    def _put(self, x):
        """Array onto this decoder's device (committed) or the default
        (uncommitted — jax places it; the pre-farm behavior)."""
        import jax
        import jax.numpy as jnp
        if self.device is None:
            return jnp.asarray(np.asarray(x))
        return jax.device_put(np.asarray(x), self.device)

    def load_params(self, arrays):
        """Swap in a new parameter set UNDER the compiled executables
        (rolling weight update). Shapes must match the serving set —
        same shapes mean the existing prefill/step executables keep
        running with zero recompiles, which is what lets a replica
        flip versions inside one drain window."""
        new = decode_params(arrays, self.cfg)
        for k, old in self.params.items():
            shp = tuple(np.asarray(new[k]).shape)
            if shp != tuple(old.shape):
                raise ValueError(
                    f"rolling update changed the shape of {k}: "
                    f"{tuple(old.shape)} -> {shp}; weight updates "
                    f"must keep the serving architecture")
        self.params = {k: self._put(v) for k, v in new.items()}

    # ---------------------------------------------------------- state
    @property
    def max_new_tokens(self):
        """Generated-token capacity per slot (position 0 is bos)."""
        return self.max_len - 1

    def init_state(self):
        """Fresh device-resident slot state (all slots free/garbage).
        Keys: kc/vc [L,S,T,H,Dh] self-attn caches (or, with
        kv_quant="int8", kc_q/vc_q int8 codes + kc_s/vc_s fp32 absmax
        scales [L,S,T,H,Dh/kv_block]), ck/cv [L,S,Ts,H,Dh] cross-attn
        caches, src_bias [S,1,1,Ts]."""
        import jax
        import jax.numpy as jnp
        cfg = self.cfg
        L, S = cfg.n_layer, self.num_slots
        H, Dh = cfg.n_head, cfg.d_model // cfg.n_head
        T, Ts = self.max_len, self.src_max_len
        z = jnp.zeros
        if self.kv_quant == "int8":
            nb = Dh // self.kv_block
            state = {"kc_q": z((L, S, T, H, Dh), jnp.int8),
                     "kc_s": z((L, S, T, H, nb), jnp.float32),
                     "vc_q": z((L, S, T, H, Dh), jnp.int8),
                     "vc_s": z((L, S, T, H, nb), jnp.float32),
                     "ck": z((L, S, Ts, H, Dh), jnp.float32),
                     "cv": z((L, S, Ts, H, Dh), jnp.float32),
                     "src_bias": z((S, 1, 1, Ts), jnp.float32)}
        else:
            state = {"kc": z((L, S, T, H, Dh), jnp.float32),
                     "vc": z((L, S, T, H, Dh), jnp.float32),
                     "ck": z((L, S, Ts, H, Dh), jnp.float32),
                     "cv": z((L, S, Ts, H, Dh), jnp.float32),
                     "src_bias": z((S, 1, 1, Ts), jnp.float32)}
        if self.device is not None:
            state = {k: jax.device_put(v, self.device)
                     for k, v in state.items()}
        return state

    def kv_cache_bytes(self):
        """Analytic slot-state footprint in bytes (self-attn codes +
        scales, cross-attn caches, src bias) — the per-replica
        capacity number behind tpustat's KV column and the
        slots-per-device bench curve; int8 shrinks the self-attn term
        ~4x (codes) minus the scale overhead."""
        cfg = self.cfg
        L, S = cfg.n_layer, self.num_slots
        H, Dh = cfg.n_head, cfg.d_model // cfg.n_head
        T, Ts = self.max_len, self.src_max_len
        n_self = L * S * T * H * Dh
        if self.kv_quant == "int8":
            self_b = 2 * (n_self + (n_self // self.kv_block) * 4)
        else:
            self_b = 2 * n_self * 4
        cross_b = 2 * L * S * Ts * H * Dh * 4
        return self_b + cross_b + S * Ts * 4

    # ------------------------------------------------------- math core
    @staticmethod
    def _pe(T, D):
        """Sinusoidal table [T, D], bitwise the add_position_encoding
        kernel's (jnp on device; constant-folded into the jit)."""
        import jax.numpy as jnp
        pos = jnp.arange(T, dtype=jnp.float32)[:, None]
        i = jnp.arange(D // 2, dtype=jnp.float32)[None, :]
        angle = pos / jnp.power(10000.0, 2 * i / D)
        return jnp.concatenate([jnp.sin(angle), jnp.cos(angle)],
                               axis=-1)

    @staticmethod
    def _ln(x, scale, bias, eps=1e-5):
        """layer_norm kernel's jnp path (f32 internals, last axis)."""
        import jax
        import jax.numpy as jnp
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + eps)
        return (y * scale.reshape(-1) + bias.reshape(-1)).astype(x.dtype)

    @staticmethod
    def _fc(x, w, b=None, relu=False):
        """mul-kernel matmul (2-D flatten) + bias + activation."""
        import jax
        lead = x.shape[:-1]
        out = x.reshape((-1, x.shape[-1])) @ w
        out = out.reshape(lead + (w.shape[1],))
        if b is not None:
            out = out + b
        if relu:
            out = jax.nn.relu(out)
        return out

    def _build_prefill(self, rows):
        """Encoder forward + cross K/V projections for `rows` padded
        sequences; jitted per distinct row count (bucketed upstream)."""
        import jax
        import jax.numpy as jnp
        from ..ops.kernels_nn import _attn_softmax
        cfg = self.cfg
        L, H = cfg.n_layer, cfg.n_head
        D = cfg.d_model
        Dh = D // H
        Ts = self.src_max_len
        scale = Dh ** -0.5
        sqrt_d = float(np.sqrt(D))
        fc, ln = self._fc, self._ln

        def prefill(p, src, src_len):
            mask = (jnp.arange(Ts)[None, :]
                    < src_len[:, None]).astype(jnp.float32)
            src_bias = (mask * jnp.asarray(1e9, jnp.float32)
                        + jnp.asarray(-1e9, jnp.float32))[:, None, None, :]
            ids = jnp.clip(src.astype(jnp.int32), 0,
                           cfg.src_vocab - 1)
            x = jnp.take(p["src_emb.w_0"], ids, axis=0)
            x = x * jnp.asarray(sqrt_d, x.dtype)
            x = x + self._pe(Ts, D)[None].astype(x.dtype)
            for i in range(L):
                res = x
                q = fc(x, p[f"enc{i}_q.w_0"]).reshape(rows, Ts, H, Dh)
                k = fc(x, p[f"enc{i}_k.w_0"]).reshape(rows, Ts, H, Dh)
                v = fc(x, p[f"enc{i}_v.w_0"]).reshape(rows, Ts, H, Dh)
                logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(
                    jnp.float32) * jnp.asarray(scale, jnp.float32)
                logits = logits + src_bias
                w = _attn_softmax(logits).astype(x.dtype)
                o = jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(
                    rows, Ts, H * Dh)
                x = ln(fc(o, p[f"enc{i}_o.w_0"]) + res,
                       p[f"layer_norm_{_ln_index(cfg, 'enc', i, 'attn')}.w_0"],
                       p[f"layer_norm_{_ln_index(cfg, 'enc', i, 'attn')}.b_0"])
                res = x
                h = fc(x, p[f"enc{i}_ffn_fc1.w_0"],
                       p[f"enc{i}_ffn_fc1.b_0"], relu=True)
                h = fc(h, p[f"enc{i}_ffn_fc2.w_0"],
                       p[f"enc{i}_ffn_fc2.b_0"])
                x = ln(h + res,
                       p[f"layer_norm_{_ln_index(cfg, 'enc', i, 'ffn')}.w_0"],
                       p[f"layer_norm_{_ln_index(cfg, 'enc', i, 'ffn')}.b_0"])
            ck = jnp.stack([fc(x, p[f"dec{i}_cross_k.w_0"]).reshape(
                rows, Ts, H, Dh) for i in range(L)])
            cv = jnp.stack([fc(x, p[f"dec{i}_cross_v.w_0"]).reshape(
                rows, Ts, H, Dh) for i in range(L)])
            return ck, cv, src_bias

        return jax.jit(prefill)

    def _build_step(self):
        import jax
        import jax.numpy as jnp
        from ..ops.kernels_nn import _attn_softmax
        cfg = self.cfg
        L, H = cfg.n_layer, cfg.n_head
        D = cfg.d_model
        Dh = D // H
        S, T = self.num_slots, self.max_len
        V = cfg.trg_vocab
        scale = Dh ** -0.5
        sqrt_d = float(np.sqrt(D))
        topk, temp = self.topk, self.temperature
        fc, ln = self._fc, self._ln
        quant = self.kv_quant == "int8"
        ret_logits = self.return_logits
        B = self.kv_block
        # trace-time kern-registry consult (ops.registry.accel): the
        # single-token ragged decode kernel for the fp32 cache, the
        # fused dequantize-attend for the int8 cache. Each call below
        # still self-gates (try_* convention) — None keeps the exact
        # jnp composition.
        from ..ops.registry import accel as _accel, lowering_for
        fused_dequant = _accel("dequant_attend_int8") if quant else None
        fused_decode = None if quant else _accel("decode_attend")

        if quant:
            # the int8 KV path is the ONLY importer of gradsync here:
            # fp32 decode must not load the collective machinery
            # (lazily-imported pin in tests/test_bench_contract.py)
            from ..parallel.gradsync import quantize_int8_blockwise

            def cache_write(c, i, rows, pos, new):
                # new [S,H,Dh] -> int8 codes + per-block absmax scales
                # (gradsync's wire format, block = kv_block head lanes)
                cq, cs = c
                q8, sc = quantize_int8_blockwise(new.reshape(-1),
                                                 block_size=B)
                return (cq.at[i, rows, pos].set(q8.reshape(S, H, Dh)),
                        cs.at[i, rows, pos].set(
                            sc.reshape(S, H, Dh // B)))

            def cache_read(c, i):
                # dequantize in-graph at attention time: codes * scale
                # broadcast over each block -> fp32 [S,T,H,Dh]
                cq, cs = c
                f = cq[i].astype(jnp.float32).reshape(
                    S, T, H, Dh // B, B) * cs[i][..., None]
                return f.reshape(S, T, H, Dh)
        else:
            def cache_write(c, i, rows, pos, new):
                return (c[0].at[i, rows, pos].set(new),)

            def cache_read(c, i):
                return c[0][i]

        def body(*args):
            # the registry consults inside follow this decoder's device
            # (device=None: the process default)
            with lowering_for(getattr(self.device, "platform", None)):
                return _body(*args)

        def _body(p, kcache, vcache, ck, cv, src_bias, ids, pos, seed):
            rows = jnp.arange(S)
            x = jnp.take(p["trg_emb.w_0"],
                         jnp.clip(ids.astype(jnp.int32), 0, V - 1),
                         axis=0)                              # [S, D]
            x = x * jnp.asarray(sqrt_d, x.dtype)
            x = x + jnp.take(self._pe(T, D).astype(x.dtype), pos, axis=0)
            keep = (jnp.arange(T)[None, :]
                    <= pos[:, None])[:, None, None, :]   # [S,1,1,T]
            for i in range(L):
                res = x
                q = fc(x, p[f"dec{i}_self_q.w_0"]).reshape(S, 1, H, Dh)
                kn = fc(x, p[f"dec{i}_self_k.w_0"]).reshape(S, H, Dh)
                vn = fc(x, p[f"dec{i}_self_v.w_0"]).reshape(S, H, Dh)
                kcache = cache_write(kcache, i, rows, pos, kn)
                vcache = cache_write(vcache, i, rows, pos, vn)
                o = None
                if fused_dequant is not None:
                    # int8 codes + scales stream straight into the
                    # kernel — no fp32 cache copy materializes
                    got = fused_dequant(q.reshape(S, H, Dh),
                                        kcache[0][i], kcache[1][i],
                                        vcache[0][i], vcache[1][i],
                                        pos, scale)
                    if got is not None:
                        o = got.astype(x.dtype).reshape(S, H * Dh)
                elif fused_decode is not None:
                    got = fused_decode(q.reshape(S, H, Dh),
                                       kcache[0][i], vcache[0][i],
                                       pos, scale)
                    if got is not None:
                        o = got.astype(x.dtype).reshape(S, H * Dh)
                if o is None:
                    logits = jnp.einsum("bqhd,bkhd->bhqk", q,
                                        cache_read(kcache, i)).astype(
                        jnp.float32) * jnp.asarray(scale, jnp.float32)
                    logits = jnp.where(keep, logits, -jnp.inf)
                    w = _attn_softmax(logits).astype(x.dtype)
                    o = jnp.einsum("bhqk,bkhd->bqhd", w,
                                   cache_read(vcache, i)).reshape(
                        S, H * Dh)
                x = ln(fc(o, p[f"dec{i}_self_o.w_0"]) + res,
                       p[f"layer_norm_{_ln_index(cfg, 'dec', i, 'self')}.w_0"],
                       p[f"layer_norm_{_ln_index(cfg, 'dec', i, 'self')}.b_0"])
                res = x
                q = fc(x, p[f"dec{i}_cross_q.w_0"]).reshape(S, 1, H, Dh)
                logits = jnp.einsum("bqhd,bkhd->bhqk", q, ck[i]).astype(
                    jnp.float32) * jnp.asarray(scale, jnp.float32)
                logits = logits + src_bias
                w = _attn_softmax(logits).astype(x.dtype)
                o = jnp.einsum("bhqk,bkhd->bqhd", w, cv[i]).reshape(
                    S, H * Dh)
                x = ln(fc(o, p[f"dec{i}_cross_o.w_0"]) + res,
                       p[f"layer_norm_{_ln_index(cfg, 'dec', i, 'cross')}.w_0"],
                       p[f"layer_norm_{_ln_index(cfg, 'dec', i, 'cross')}.b_0"])
                res = x
                h = fc(x, p[f"dec{i}_ffn_fc1.w_0"],
                       p[f"dec{i}_ffn_fc1.b_0"], relu=True)
                h = fc(h, p[f"dec{i}_ffn_fc2.w_0"],
                       p[f"dec{i}_ffn_fc2.b_0"])
                x = ln(h + res,
                       p[f"layer_norm_{_ln_index(cfg, 'dec', i, 'ffn')}.w_0"],
                       p[f"layer_norm_{_ln_index(cfg, 'dec', i, 'ffn')}.b_0"])
            logits = fc(x, p["proj.w_0"])                  # [S, V]
            if topk and topk > 1:
                vals, cand = jax.lax.top_k(logits, topk)
                key = jax.random.PRNGKey(seed)
                choice = jax.random.categorical(
                    key, vals.astype(jnp.float32)
                    / jnp.asarray(temp, jnp.float32), axis=-1)
                nxt = jnp.take_along_axis(
                    cand, choice[:, None], axis=-1)[:, 0]
            else:
                nxt = jnp.argmax(logits, axis=-1)
            return (kcache, vcache, nxt.astype(jnp.int32),
                    logits.astype(jnp.float32))

        # flat signatures so donation sees individual cache buffers;
        # donating the caches keeps the update in place
        if quant:
            def step(p, kc_q, kc_s, vc_q, vc_s, ck, cv, src_bias,
                     ids, pos, seed):
                kcache, vcache, nxt, lg = body(
                    p, (kc_q, kc_s), (vc_q, vc_s), ck, cv, src_bias,
                    ids, pos, seed)
                out = kcache + vcache + (nxt,)
                return out + (lg,) if ret_logits else out
            donate = (1, 2, 3, 4)
        else:
            def step(p, kc, vc, ck, cv, src_bias, ids, pos, seed):
                kcache, vcache, nxt, lg = body(
                    p, (kc,), (vc,), ck, cv, src_bias, ids, pos, seed)
                out = kcache + vcache + (nxt,)
                return out + (lg,) if ret_logits else out
            donate = (1, 2)
        return jax.jit(step, donate_argnums=donate)

    # ------------------------------------------------- compile sharing
    def _build_key(self, kind, rows=None):
        """Structural identity of a jitted function — everything its
        closure bakes in. Two decoders with equal keys can share the
        trace (jax still specializes executables per device placement
        under the hood); params are runtime args, so the key excludes
        them and rolling updates never re-key."""
        cfg = self.cfg
        if kind == "prefill":
            return ("prefill", cfg.src_vocab, cfg.d_model, cfg.n_head,
                    cfg.n_layer, self.src_max_len, int(rows))
        return ("step", cfg.trg_vocab, cfg.d_model, cfg.n_head,
                cfg.n_layer, self.num_slots, self.max_len,
                self.src_max_len, self.topk, self.temperature,
                self.kv_quant, self.kv_block, self.return_logits)

    def _get_or_build(self, kind, rows=None):
        build = (lambda: self._build_prefill(rows)) \
            if kind == "prefill" else self._build_step
        if self._build_cache is None:
            self.compile_count += 1
            return build()
        fn, built = self._build_cache.get_or_build(
            self._build_key(kind, rows), build)
        if built:
            self.compile_count += 1
        return fn

    # --------------------------------------------------------- running
    def prefill(self, src, src_len):
        """Run the encoder for `rows = src.shape[0]` sequences (pad
        rows upstream to a fixed bucket set to bound compiles). src
        must be padded to src_max_len. Returns (ck, cv, src_bias)
        shaped [L, rows, Ts, H, Dh] / [rows, 1, 1, Ts]."""
        import jax.numpy as jnp
        src = np.asarray(src)
        rows, Ts = src.shape
        if Ts != self.src_max_len:
            raise ValueError(f"src padded to {Ts}, decoder built for "
                             f"src_max_len={self.src_max_len}")
        fn = self._prefill_jit.get(rows)
        own = _tm.compiles.NO_OWNER
        if fn is None:
            fn = self._get_or_build("prefill", rows)
            self._prefill_jit[rows] = fn
            # a bucket's first call compiles: the compile log puts it
            # down to the bucket
            own = _tm.compile_owner(f"decode.prefill:{rows}")
        with own:
            return fn(self.params, jnp.asarray(src.astype(np.int32)),
                      jnp.asarray(np.asarray(src_len).astype(np.int32)))

    def write_slots(self, state, prefill_out, slots):
        """Scatter `len(slots)` prefilled rows into the slot state
        (device-side; the extra bucket-pad rows are dropped)."""
        import jax.numpy as jnp
        ck, cv, src_bias = prefill_out
        n = len(slots)
        # eager slices and scatters: each new row count compiles its
        # own small programs, which the compile log counts under this
        # owner (telemetry.compile_log)
        with _tm.compile_owner("decode.write_slots"):
            idx = jnp.asarray(np.asarray(slots, np.int32))
            state["ck"] = state["ck"].at[:, idx].set(ck[:, :n])
            state["cv"] = state["cv"].at[:, idx].set(cv[:, :n])
            state["src_bias"] = state["src_bias"].at[idx].set(
                src_bias[:n])
        return state

    def step(self, state, ids, pos, seed=0):
        """One decode iteration for ALL slots: feed the current token
        id + position per slot, get the next token id per slot (numpy
        int32 [num_slots]). Caches update in place in `state`. Free /
        inactive slots compute garbage lanes that the scheduler
        ignores — the price of a static shape, and exactly one
        compiled executable."""
        import jax.numpy as jnp
        own = _tm.compiles.NO_OWNER
        if self._step_jit is None:
            self._step_jit = self._get_or_build("step")
            own = _tm.compile_owner("decode.step")   # compiles below
        feed = (jnp.asarray(np.asarray(ids, np.int32)),
                jnp.asarray(np.asarray(pos, np.int32)),
                jnp.asarray(np.uint32(seed)))
        # the self-attention caches go in donated and come back first
        caches = ("kc_q", "kc_s", "vc_q", "vc_s") \
            if self.kv_quant == "int8" else ("kc", "vc")
        with own:
            out = self._step_jit(
                self.params, *(state[k] for k in caches), state["ck"],
                state["cv"], state["src_bias"], *feed)
        for k, v in zip(caches, out):
            state[k] = v
        nxt = out[len(caches)]
        if self.return_logits:
            self.last_logits = np.asarray(out[-1])
        return np.asarray(nxt)
