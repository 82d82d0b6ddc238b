"""The table of peaks and the counts of needed work (FLOPs, bytes).

Counts are of what the algorithm needs whatever implements it: matrix
products and attention, 2 FLOPs a multiply-add, no recomputation; the
backward pass is twice the forward. Decoder self-attention counts the
causal half. Embedding look-ups, LayerNorm, softmax and the optimizer are
not counted, so a share of the peak computed from these can only read low.

A kernel's work is a list of (FLOPs, bytes, calls) a step, one entry for
each shape it is called at; its floor is the least time the chip could
take: for each call the larger of FLOPs over the peak and bytes over the
peak bandwidth, the arrays the algorithm must read and write counted once
each. `<kernel>_roofline` is that floor over the kernel's traced time.
"""

PEAKS = {
    # device_kind: bf16 FLOP/s, HBM bytes/s, HBM bytes.
    # Source: Google Cloud TPU v5e documentation ("TPU v5e": 197 TFLOP/s
    # bf16, 819 GB/s, 16 GB per chip).
    "TPU v5 lite": {"flops": 197e12, "bytes_per_s": 819e9, "hbm": 16e9},
}


def peak(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"device kind {device_kind!r} is not in chipbench's "
                       f"table of peaks (chipbench/counts.py)") from None


def forward_flops(cfg, rows, src_len, trg_len):
    """One forward pass over `rows` pairs of src_len/trg_len tokens."""
    d, di, L, V = (cfg["d_model"], cfg["d_inner"], cfg["n_layer"],
                   cfg["trg_vocab"])
    ffn = 4 * d * di
    enc_tok = 6 * d * d + 2 * d * d + ffn + 4 * src_len * d
    dec_tok = (8 * d * d + 2 * trg_len * d          # causal self-attention
               + 4 * d * d + 4 * src_len * d        # cross q, o and scores
               + ffn)
    cross_kv_tok = 4 * d * d                        # per source token
    return rows * (L * (src_len * (enc_tok + cross_kv_tok)
                        + trg_len * dec_tok)
                   + trg_len * 2 * d * V)


def train_step_flops(cfg, rows, src_len, trg_len):
    return 3 * forward_flops(cfg, rows, src_len, trg_len)


ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def attention_work(rows, heads, q_len, kv_len, head_dim, itemsize, causal,
                   backward):
    """(FLOPs, bytes) of one attention over [rows, q_len, heads x head_dim]
    queries and [rows, kv_len, ...] keys and values. Forward: the two
    products q k^T and p v, 4 B H T S D (the causal half where causal);
    q, k, v and out once each. Backward: the five products of dq, dk, dv
    (the scores once more, dp, then one each), 2.5 times the forward's;
    q, k, v, out, dout, dq, dk, dv once each."""
    flops = 4 * rows * heads * q_len * kv_len * head_dim
    if causal:
        flops //= 2
    q_bytes = rows * q_len * heads * head_dim * itemsize
    kv_bytes = rows * kv_len * heads * head_dim * itemsize
    if backward:
        return flops * 5 // 2, 4 * q_bytes + 4 * kv_bytes
    return flops, 2 * q_bytes + 2 * kv_bytes


def nmt_attention_calls(cfg, traffic, backward):
    """[(FLOPs, bytes, calls)] of the attentions of one transformer-base
    step: in each of `n_layer` layers one encoder self-attention, one
    causal decoder self-attention and one cross-attention."""
    B, Ts, Tt = traffic["batch_rows"], traffic["src_len"], traffic["trg_len"]
    H, L = cfg["n_head"], cfg["n_layer"]
    D = cfg["d_model"] // H
    size = ITEMSIZE[cfg["precision"]["activations"]]
    return [attention_work(B, H, Ts, Ts, D, size, False, backward) + (L,),
            attention_work(B, H, Tt, Tt, D, size, True, backward) + (L,),
            attention_work(B, H, Tt, Ts, D, size, False, backward) + (L,)]


def floor_seconds(calls, device_kind):
    """The least time a chip of that kind could take over `calls`."""
    pk = peak(device_kind)
    return sum(n * max(flops / pk["flops"], nbytes / pk["bytes_per_s"])
               for flops, nbytes, n in calls)
