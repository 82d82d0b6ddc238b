"""Mixture-of-experts ops: the router and the expert layer of the experts
held here.

The deployment these are written for divides each expert layer over
several chips (expert parallelism): the router keeps the model's whole
width (it scores ALL experts and picks the top k of them), and
`moe_expert_ffn` is told which experts this chip holds, computes their
part of the weighted sum and leaves out what the absent experts would
have added. Nothing stands in for the other chips. No token is dropped,
whatever the imbalance, and every shape is static: the grouped products
(ops/pallas/grouped_matmul.py) walk a buffer of the worst case's rows and
skip what is not in use. (`parallel/moe.py` is the older Switch layer:
top-1 with capacity drops, outside the Program IR.)
"""
import jax
import jax.numpy as jnp

from .registry import kernel


@kernel("moe_route")
def _moe_route(ctx, ins, attrs):
    """X [..., H], Weight [H, E], optional Bias [E] -> TopkIdx [..., k]
    (int32, ids over all E experts), TopkW [..., k] (float32).

    s = sigmoid(x Weight), or with `scoring: softmax` the softmax of
    x Weight over all E; the k experts are chosen by s + Bias (the
    load-balancing bias only selects; among equal values the lower id
    wins), the weights are s of the chosen, renormalised to sum to one
    (`norm_topk_prob`) and scaled. All of it float32: a router that
    rounds its scores picks other experts."""
    x, w = ins["X"][0], ins["Weight"][0]
    logits = jnp.einsum("...h,he->...e", x.astype(jnp.float32),
                        w.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    scoring = attrs.get("scoring", "sigmoid")
    if scoring not in ("sigmoid", "softmax"):
        raise ValueError(f"moe_route scores by sigmoid or softmax, not "
                         f"{scoring!r}")
    s = jax.nn.sigmoid(logits) if scoring == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    bias = ins.get("Bias")
    sel = s + bias[0].astype(jnp.float32) if bias else s
    _, idx = jax.lax.top_k(jax.lax.stop_gradient(sel), attrs["k"])
    tw = jnp.take_along_axis(s, idx, axis=-1)
    if attrs.get("norm_topk_prob", True):
        tw = tw / (jnp.sum(tw, axis=-1, keepdims=True) + 1e-6)
    tw = tw * attrs.get("routed_scaling_factor", 1.0)
    return {"TopkIdx": [idx.astype(jnp.int32)], "TopkW": [tw]}


@kernel("moe_expert_ffn")
def _moe_expert_ffn(ctx, ins, attrs):
    """X [..., H], TopkIdx / TopkW [..., k], W1 / W3 [E_held, H, F], W2
    [E_held, F, H] -> Out [..., H]: the sum over the pairs (token, expert)
    whose expert is one of `first_expert .. first_expert + E_held - 1` of
    weight * (silu(x W1[e]) * (x W3[e])) W2[e]. LocalPairs and
    MaxExpertPairs (int32 scalars) count those pairs and the fullest
    held expert's: the layer's load, computed where the routing is."""
    from .pallas import grouped_matmul as gm
    x, idx, tw = ins["X"][0], ins["TopkIdx"][0], ins["TopkW"][0]
    w1, w3, w2 = ins["W1"][0], ins["W3"][0], ins["W2"][0]
    H, k = x.shape[-1], idx.shape[-1]
    args = (x.reshape(-1, H), idx.reshape(-1, k), tw.reshape(-1, k),
            w1, w3, w2)
    first = attrs.get("first_expert", 0)
    got = None
    fused = ctx.accel("moe_expert_ffn")
    if fused is not None:
        got = fused(*args, first_expert=first)
    if got is None:
        got = gm.expert_ffn_reference(*args, first_expert=first)
    out, counts = got
    return {"Out": [out.reshape(x.shape)],
            "LocalPairs": [jnp.sum(counts, dtype=jnp.int32)],
            "MaxExpertPairs": [jnp.max(counts).astype(jnp.int32)]}
