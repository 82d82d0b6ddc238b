"""Driver for the training cells: `Executor.run` on one chip.

What is one model's -- how its program is declared, its parameters, its
batches, what a step counts as, its plain reference -- is behind the
module that the configuration's `model` key names (`models/<model>.py`;
no key means `nmt`). Everything that defines the measurement is here, once.

Set-up builds one object -- the program, its executor and its scope --
puts the benchmark's seeded weights into the scope, drives it through its
first three steps on three different batches through the very call the
window uses, reads what `correct` compares (each step's loss, the first
gradient's norm per leaf out of Adam's first moment, the parameters'
change per leaf after three steps), warms up, and hands the same object to
the window. The plain reference follows the same three steps after the
window has closed, the peak has been read and the program's state freed.
"""
import gc
import time

import numpy as np

from chipbench import correct, trace


def _norms(model, tree):
    import jax
    return jax.jit(model.tree_norms)(tree)


def _delta_norms(model, after, before):
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda a, b: model.tree_norms(
        {k: a[k].astype(jnp.float32) - b[k].astype(jnp.float32)
         for k in a}))(after, before)


def _host(tree):
    import jax
    return {k: float(v) for k, v in jax.device_get(tree).items()}


class Trainer:
    """The program under test: one compiled step with its state."""

    def __init__(self, ctx, model):
        import paddle_tpu as fluid
        cfg = ctx.cfg
        self.fluid = fluid
        self.main, startup, self.loss = model.build(cfg, ctx.traffic, fluid)
        ctx.mark("program declared")
        # a fixed seed for the program's own generator: every parameter the
        # startup program draws is overwritten below by the benchmark's
        # seeded weights, and a seed baked into the startup module would
        # make every new --seed compile it anew (12 s, my chip run, PR 26)
        self.main.random_seed = startup.random_seed = 1
        if cfg["precision"]["activations"] == "bfloat16":
            fluid.amp.cast_program_to_bf16(self.main)
        if cfg["entry"] != "Executor.run":
            raise RuntimeError(f"this driver times Executor.run, not "
                               f"{cfg['entry']!r}")
        place = fluid.TPUPlace(0) if ctx.on_chip else fluid.CPUPlace()
        self.scope = fluid.Scope()
        self.exe = fluid.Executor(place)
        with fluid.scope_guard(self.scope):
            self.exe.run(startup)
        ctx.mark("startup program run")
        self.names = [n for n, _, _ in model.param_specs(cfg)]
        declared = {v.name for v in self.main.all_parameters()}
        if declared != set(self.names):
            raise RuntimeError(
                "the program's parameters are not the benchmark's: "
                f"{sorted(declared ^ set(self.names))[:6]}")
        params = model.make_params(cfg, ctx.seed,
                                   cfg["precision"]["parameters"])
        for name, arr in params.items():
            self.scope.set(name, arr)
        ctx.mark("weights made")

    def step(self, feed):
        """One training step through the entry the cell times; the loss
        comes back to the host, as a trainer's loop reads it."""
        with self.fluid.scope_guard(self.scope):
            out = self.exe.run(self.main, feed=feed, fetch_list=[self.loss])
        return float(np.asarray(out[0]).reshape(-1)[0])

    def state(self, suffix=""):
        return {n: self.scope.get(n + suffix) for n in self.names}


def first_steps(trainer, ctx, model, batches):
    """Steps 1-3 through the window's own call; what `correct` compares."""
    cfg = ctx.cfg
    seen = {"loss": []}
    seen["loss"].append(trainer.step(batches[0]))
    ctx.mark("first step")
    m1 = _host(_norms(model, trainer.state("_moment1_0")))
    b1 = cfg["optimizer"]["beta1"]
    seen["grad_norm"] = {k: v / (1.0 - b1) for k, v in m1.items()}
    for b in batches[1:3]:
        seen["loss"].append(trainer.step(b))
    before = model.make_params(cfg, ctx.seed, cfg["precision"]["parameters"])
    seen["delta_norm"] = _host(_delta_norms(model, trainer.state(), before))
    return seen


def run(ctx):
    import jax
    cfg, traffic = ctx.cfg, ctx.traffic
    model = ctx.man.model(cfg.get("model", "nmt"))
    batches = model.make_batches(traffic, cfg, ctx.seed)
    trainer = Trainer(ctx, model)
    seen = first_steps(trainer, ctx, model, batches)
    ctx.mark("three checked steps")
    warm = traffic.get("warm_steps", 2)
    for i in range(warm):
        trainer.step(batches[(3 + i) % len(batches)])
    annotate = jax.profiler.TraceAnnotation
    if ctx.trace:
        trace.start(ctx.trace_dir)
    req0 = ctx.watch.requests()
    setup_s = time.perf_counter() - ctx.t_start
    steps, i = 0, 3 + warm
    with annotate("cb/window"):
        t_open = time.perf_counter()
        while True:
            with annotate("cb/exe.run"):
                trainer.step(batches[i % len(batches)])
            steps += 1
            i += 1
            window_s = time.perf_counter() - t_open
            if window_s >= ctx.seconds:
                break
    compiles = ctx.watch.requests() - req0
    reduced = None
    if ctx.trace:
        trace.stop()
        reduced = trace.reduce(trace.read_xplane(ctx.trace_dir), window_s)
    peak = ctx.memory_peak()
    tokens = steps * model.tokens_per_step(traffic)
    ctx.say(f"window {window_s:.3f} s, {steps} steps, "
            f"{tokens / window_s:.1f} target tokens/s, setup {setup_s:.1f} s,"
            f" {compiles} compile request(s) in the window")

    # free the program's state, then the reference
    trainer.scope = trainer.exe = None
    del trainer
    gc.collect()
    jax.clear_caches()
    t0 = time.perf_counter()
    dtype = cfg["precision"]["parameters"]
    params = model.make_params(cfg, ctx.seed, dtype)
    block = traffic.get("reference_block_rows", 32)
    ref = model.reference_steps(params, cfg, batches[:3], cfg["optimizer"],
                                "float32", block)
    numbers = correct.train_numbers(seen, ref)
    ctx.say(f"reference: 3 steps in {time.perf_counter() - t0:.1f} s; "
            f"loss program {seen['loss']} reference {ref['loss']}")
    control = None
    if ctx.control:
        control = {}
        low = model.reference_steps(params, cfg, batches[:3],
                                    cfg["optimizer"],
                                    cfg["precision"]["control"], block)
        control[cfg["precision"]["control"]] = correct.train_numbers(low, ref)
        still = model.reference_steps(
            params, cfg, batches[:3], dict(cfg["optimizer"], lr=0.0),
            "float32", block)
        control["state_unchanged"] = correct.train_numbers(still, ref)
        n_rows = len(next(iter(batches[0].values())))
        half = model.reference_steps(
            params, cfg, batches[:3], cfg["optimizer"], "float32", block,
            rows=slice(0, n_rows // 2))
        control["half_batch"] = correct.train_numbers(half, ref)
        ctx.say(f"control: {control}")

    facts = {"kind": "train", "cfg": cfg, "traffic": traffic,
             "window_s": window_s, "steps": steps, "tokens": tokens,
             "chips": len(ctx.devices), "compiles_in_window": compiles,
             "device_kind": ctx.devices[0].device_kind,
             "on_chip": ctx.on_chip, "memory_peak_bytes": peak,
             "trace": reduced,
             "step_flops": model.step_flops(cfg, traffic),
             "kernel_work": model.kernel_work(cfg, traffic)
             if hasattr(model, "kernel_work") else {}}
    return {"attempted": steps, "failed": 0, "numbers": numbers,
            "end_to_end": {"train_tokens_per_s": tokens / window_s,
                           "setup_s": setup_s},
            "facts": facts, "memory_peak_bytes": peak, "control": control}
