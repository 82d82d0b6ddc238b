"""The table of peaks and the counts of needed work (FLOPs, bytes).

Counts are of what the algorithm needs whatever implements it: matrix
products and attention, 2 FLOPs a multiply-add, no recomputation; the
backward pass is twice the forward. Decoder self-attention counts the
causal half. Embedding look-ups, LayerNorm, softmax and the optimizer are
not counted, so a share of the peak computed from these can only read low.
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
