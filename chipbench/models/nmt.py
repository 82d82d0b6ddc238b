"""Model `nmt`: transformer-base as the Fluid book builds it, for the
training driver (`entries/train.py`, which finds this file through the
configuration's `model` key; no key means this one).

A model file says what the driver cannot know of the model it trains:

    build(cfg, traffic, fluid) -> (main, startup, loss), optimizer attached
    param_specs(cfg)           -> [(name, shape, kind)] as the program
                                  declares them
    make_params(cfg, seed, dtype) -> {name: array}, one jitted call
    make_batches(traffic, cfg, seed) -> the list of feeds; every array of a
                                  feed has the batch's rows on its first axis
    tokens_per_step(traffic)   -> what `train_tokens_per_s` counts a step as
    step_flops(cfg, traffic)   -> the needed FLOPs of one step (train_mfu)
    reference_steps(params, cfg, batches, opt, prec, block_rows, rows=None)
                               -> the plain reference's {"loss", "grad_norm",
                                  "delta_norm"} over the same steps
    tree_norms(tree)           -> {leaf: norm}, as the reference takes them
    kernel_work(cfg, traffic)  -> optional: {kernel name: [(flops, bytes,
                                  calls)]} a step, for `<kernel>_roofline`

This one only points at where each of those already is.
"""
from chipbench import counts, loadgen, weights
from chipbench.reference import nmt as reference

param_specs = weights.param_specs
make_params = weights.make_params
make_batches = loadgen.make_train_batches
reference_steps = reference.train_steps
tree_norms = reference.tree_norms


def build(cfg, traffic, fluid):
    from paddle_tpu.models import transformer as tfm
    model = tfm.TransformerConfig(
        src_vocab=cfg["src_vocab"], trg_vocab=cfg["trg_vocab"],
        max_len=cfg["max_len"], d_model=cfg["d_model"],
        d_inner=cfg["d_inner"], n_head=cfg["n_head"],
        n_layer=cfg["n_layer"], dropout=cfg["dropout"],
        label_smooth_eps=cfg["label_smooth_eps"],
        fused_qkv=cfg["fused_qkv"])
    opt = cfg["optimizer"]
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            _feeds, loss, _tok = tfm.build_program(
                model, maxlen=traffic["src_len"])
            fluid.optimizer.Adam(
                opt["lr"], beta1=opt["beta1"], beta2=opt["beta2"],
                epsilon=opt["epsilon"]).minimize(loss)
    return main, startup, loss


def tokens_per_step(traffic):
    return traffic["batch_rows"] * traffic["trg_len"]


def step_flops(cfg, traffic):
    return counts.train_step_flops(cfg, traffic["batch_rows"],
                                   traffic["src_len"], traffic["trg_len"])


def kernel_work(cfg, traffic):
    return {"flash_attention_short_fwd":
            counts.nmt_attention_calls(cfg, traffic, backward=False),
            "flash_attention_short_bwd":
            counts.nmt_attention_calls(cfg, traffic, backward=True)}
