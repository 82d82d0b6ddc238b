"""bench.py's contract: ONE process, one JSON line that names the
device it ran on, and no number without a chip unless a CPU run was
asked for by name (JAX_PLATFORMS=cpu — plumbing evidence, labelled
`"platform": "cpu"`). The rest of this file pins that default-off
features leave the default paths untouched.

Runs the real bench.py as a subprocess with BENCH_ONLY=mnist.
"""
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def _env():
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", BENCH_ONLY="mnist",
               # keep test runs out of the committed perf spine
               BENCH_HISTORY_PATH=os.devnull)
    env.pop("XLA_FLAGS", None)
    env.pop("PADDLE_TPU_TELEMETRY", None)
    return env


def _parse_last(stdout):
    lines = [l for l in stdout.strip().splitlines() if l.strip()]
    assert lines, "bench printed nothing"
    return json.loads(lines[-1])


def test_final_line_schema_on_cpu():
    """The explicit CPU run: rc 0, one result line, and the line says
    it is a CPU line."""
    # telemetry is off in _env(): the run must not grow a telemetry
    # artifact (and, via the assertions below, stdout stays pinned)
    tele_artifact = os.path.join(REPO, "BENCH_telemetry.json")
    if os.path.exists(tele_artifact):
        os.remove(tele_artifact)
    p = subprocess.run([sys.executable, BENCH], env=_env(),
                       capture_output=True, text=True, timeout=400)
    assert p.returncode == 0, p.stderr[-800:]
    assert not os.path.exists(tele_artifact), \
        "telemetry-off bench wrote BENCH_telemetry.json"
    obj = _parse_last(p.stdout)
    for key in ("metric", "value", "unit", "platform", "device_kind",
                "device_count"):
        assert key in obj, (key, obj)
    assert obj["metric"] == "transformer_base_train_tokens_per_sec"
    assert obj["platform"] == "cpu"
    assert obj["mnist_mlp_steps_per_sec"] > 0
    assert "mfu" not in obj          # no peak off the chip, no MFU
    json_lines = [l for l in p.stdout.splitlines()
                  if l.strip().startswith("{")]
    assert len(json_lines) == 1, json_lines


def test_no_chip_no_number():
    """No TPU and no explicit JAX_PLATFORMS=cpu: the run fails, names
    the platform it found, and puts no result on stdout."""
    env = _env()
    del env["JAX_PLATFORMS"]
    p = subprocess.run([sys.executable, BENCH], env=env,
                       capture_output=True, text=True, timeout=400)
    assert p.returncode != 0
    assert "no TPU" in p.stderr and "'cpu'" in p.stderr
    assert not [l for l in p.stdout.splitlines()
                if l.strip().startswith("{")], p.stdout[-400:]


def test_telemetry_off_cached_fast_path():
    """Telemetry's disabled-mode contract on the hot path: a cached
    Executor.run must register NO metrics (snapshot stays {}) and stay
    fast — the instrumentation is one flag check per site, so 100
    cached iterations of a trivial program fit a generous wall-clock
    bound even on a loaded CI box."""
    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu import layers
    from paddle_tpu import telemetry as tm
    from paddle_tpu.diagnostics import recorder as flight
    from paddle_tpu.resilience import chaos

    tm.disable()
    tm.reset()
    flight.disable()
    chaos.reset()                 # re-reads the (unset) PADDLE_TPU_CHAOS
    img = layers.data("img", shape=[8])
    out = layers.reduce_mean(layers.fc(img, size=4))
    exe = pt.Executor(pt.CPUPlace())
    exe.run(pt.default_startup_program())
    x = np.random.rand(2, 8).astype("float32")
    exe.run(feed={"img": x}, fetch_list=[out])      # compile off-clock
    t0 = time.perf_counter()
    for _ in range(100):
        exe.run(feed={"img": x}, fetch_list=[out])
    dt = time.perf_counter() - t0
    assert tm.snapshot() == {}, "telemetry-off run registered metrics"
    assert tm.iter_spans() == [], "telemetry-off run recorded spans"
    assert tm.chrome_trace()["traceEvents"] == []
    # diagnostics-off contract: no pre-step state snapshots, no finite
    # checks, no flight-recorder records (PR-4 numerics doctor)
    assert exe.diag_snapshot_count == 0, \
        "diagnostics-off run snapshotted donated state"
    assert flight.active() is None
    assert exe.last_numerics_report is None
    # resilience-off contract (PR-7 tpuchaos): with PADDLE_TPU_CHAOS
    # unset the harness stays disarmed — no faults counted, no
    # resilience.* metrics, nothing injected into the 100 cached runs
    assert chaos.armed() is False, "chaos armed with env unset"
    assert chaos.fired_count() == 0
    assert dt < 20.0, f"100 cached steps took {dt:.1f}s (bound 20s)"


def test_decode_off_paths_untouched():
    """tpudecode's off contract: a server that never attaches a
    decoder never imports the decode package (serving/__init__ must
    stay lazy), and the serving fast paths are byte-identical to the
    pre-decode ones — no new flag checks on the predict route beyond
    the existing decoder-is-None lookup."""
    code = (
        "import sys\n"
        "import paddle_tpu.serving\n"
        "import paddle_tpu.serving.server\n"
        "import paddle_tpu.serving.http\n"
        "assert 'paddle_tpu.serving.decode' not in sys.modules, "
        "'serving/__init__ eagerly imports the decode package'\n"
        "assert 'paddle_tpu.serving.decode.engine' not in sys.modules\n"
        "print('LAZY_OK')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=240)
    assert p.returncode == 0, (p.stdout[-400:], p.stderr[-800:])
    assert "LAZY_OK" in p.stdout


def test_farm_off_paths_untouched():
    """tpufarm's off contract: serving without a replica group never
    imports the farm package (single-engine deployments pay nothing),
    and the fp32 decode state schema stays byte-identical to the
    pre-farm layout — the int8 KV path is opt-in per model, never a
    default."""
    code = (
        "import sys\n"
        "import paddle_tpu.serving\n"
        "import paddle_tpu.serving.server\n"
        "import paddle_tpu.serving.http\n"
        "import paddle_tpu.serving.decode\n"
        "assert 'paddle_tpu.serving.farm' not in sys.modules, "
        "'serving eagerly imports the farm package'\n"
        "assert 'paddle_tpu.serving.farm.group' not in sys.modules\n"
        "from paddle_tpu.models import transformer as tfm\n"
        "import numpy as np\n"
        "cfg = tfm.TransformerConfig(src_vocab=16, trg_vocab=16,"
        " max_len=8, d_model=8, d_inner=16, n_head=2, n_layer=1,"
        " dropout=0.0, label_smooth_eps=0.0)\n"
        "import paddle_tpu as pt\n"
        "from paddle_tpu.core import framework as fw\n"
        "infer, start = fw.Program(), fw.Program()\n"
        "with pt.program_guard(infer, start):\n"
        "    with pt.unique_name.guard():\n"
        "        tfm.build_infer_program(cfg, maxlen=8)\n"
        "pt.Executor(pt.CPUPlace()).run(start)\n"
        "scope = pt.global_scope()\n"
        "params = {v.name: np.asarray(scope.get(v.name))"
        " for v in infer.persistable_vars()}\n"
        "dec = tfm.IncrementalDecoder(cfg, params, num_slots=2,"
        " max_len=8)\n"
        "assert set(dec.init_state()) == "
        "{'kc', 'vc', 'ck', 'cv', 'src_bias'}, "
        "'default decode state schema changed'\n"
        "assert 'paddle_tpu.serving.farm' not in sys.modules\n"
        "print('FARM_OFF_OK')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=240)
    assert p.returncode == 0, (p.stdout[-400:], p.stderr[-800:])
    assert "FARM_OFF_OK" in p.stdout


def test_guard_off_paths_untouched():
    """tpuguard's off contract (the bench-contract pin): a farm
    constructed without `guard=` never imports the serving.guard
    package — no health tracker, no token buckets, no brownout checks
    on the submit path — and the router's decision function stays the
    PR-13 shape (`health` is None, submissions route by load alone)."""
    code = (
        "import sys\n"
        "import numpy as np\n"
        "import paddle_tpu as pt\n"
        "from paddle_tpu.core import framework as fw\n"
        "from paddle_tpu.models import transformer as tfm\n"
        "from paddle_tpu.serving.farm import FarmConfig, ReplicaGroup\n"
        "from paddle_tpu.serving.decode import (DecodeConfig,"
        " DecodeEngineConfig)\n"
        "cfg = tfm.TransformerConfig(src_vocab=16, trg_vocab=16,"
        " max_len=8, d_model=8, d_inner=16, n_head=2, n_layer=1,"
        " dropout=0.0, label_smooth_eps=0.0)\n"
        "infer, start = fw.Program(), fw.Program()\n"
        "with pt.program_guard(infer, start):\n"
        "    with pt.unique_name.guard():\n"
        "        tfm.build_infer_program(cfg, maxlen=8)\n"
        "pt.Executor(pt.CPUPlace()).run(start)\n"
        "scope = pt.global_scope()\n"
        "params = {v.name: np.asarray(scope.get(v.name))"
        " for v in infer.persistable_vars()}\n"
        "group = ReplicaGroup(cfg, params, FarmConfig(replicas=2,"
        " engine=DecodeEngineConfig(num_slots=2, max_len=8,"
        " prefill_buckets=(1, 2)),"
        " decode=DecodeConfig(bos=0)), name='plain')\n"
        "assert group.guard is None\n"
        "assert group.router.health is None, "
        "'guard-off router must keep the PR-13 decision function'\n"
        "fut = group.submit(np.arange(2, 6).astype('int64'),"
        " src_len=4, max_new_tokens=3)\n"
        "for _ in range(60):\n"
        "    if fut.done():\n"
        "        break\n"
        "    group.run_iteration()\n"
        "assert len(fut.result(timeout=0).tokens) == 3\n"
        "assert 'paddle_tpu.serving.guard' not in sys.modules, "
        "'an unconfigured farm imported the guard package'\n"
        "assert 'paddle_tpu.serving.guard.health' not in sys.modules\n"
        "print('GUARD_OFF_OK')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=240,
                       cwd=REPO)
    assert p.returncode == 0, (p.stdout[-400:], p.stderr[-1200:])
    assert "GUARD_OFF_OK" in p.stdout


def test_reqtrace_off_paths_untouched():
    """tputrace's off contract (the bench-contract pin): with
    PADDLE_TPU_REQTRACE unset, serving a request through the farm
    never imports telemetry.reqtrace — every seam is one bool check —
    and flipping tracing on decodes byte-identical tokens."""
    code = (
        "import sys\n"
        "import numpy as np\n"
        "import paddle_tpu as pt\n"
        "from paddle_tpu import telemetry as tm\n"
        "from paddle_tpu.core import framework as fw\n"
        "from paddle_tpu.models import transformer as tfm\n"
        "from paddle_tpu.serving.farm import FarmConfig, ReplicaGroup\n"
        "from paddle_tpu.serving.decode import (DecodeConfig,"
        " DecodeEngineConfig)\n"
        "assert tm.reqtrace_enabled() is False\n"
        "cfg = tfm.TransformerConfig(src_vocab=16, trg_vocab=16,"
        " max_len=8, d_model=8, d_inner=16, n_head=2, n_layer=1,"
        " dropout=0.0, label_smooth_eps=0.0)\n"
        "infer, start = fw.Program(), fw.Program()\n"
        "with pt.program_guard(infer, start):\n"
        "    with pt.unique_name.guard():\n"
        "        tfm.build_infer_program(cfg, maxlen=8)\n"
        "pt.Executor(pt.CPUPlace()).run(start)\n"
        "scope = pt.global_scope()\n"
        "params = {v.name: np.asarray(scope.get(v.name))"
        " for v in infer.persistable_vars()}\n"
        "group = ReplicaGroup(cfg, params, FarmConfig(replicas=1,"
        " engine=DecodeEngineConfig(num_slots=2, max_len=8,"
        " prefill_buckets=(1, 2)),"
        " decode=DecodeConfig(bos=0)), name='quiet')\n"
        "def run(rid):\n"
        "    fut = group.submit(np.arange(2, 6).astype('int64'),"
        " src_len=4, max_new_tokens=3, request_id=rid)\n"
        "    for _ in range(60):\n"
        "        if fut.done():\n"
        "            break\n"
        "        group.run_iteration()\n"
        "    return np.asarray(fut.result(timeout=0).tokens,"
        " np.int64)\n"
        "off = run('r-off')\n"
        "assert 'paddle_tpu.telemetry.reqtrace' not in sys.modules, "
        "'trace-off serving imported the tracer'\n"
        "tm.reqtrace_enable()\n"
        "on = run('r-on')\n"
        "assert off.tobytes() == on.tobytes(), "
        "'tracing changed the decoded bytes'\n"
        "assert tm.reqtrace.trace_end('r-on') == []\n"
        "assert tm.reqtrace.snapshot()['seen'] == 1\n"
        "print('REQTRACE_OFF_OK')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PADDLE_TPU_REQTRACE", None)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=240,
                       cwd=REPO)
    assert p.returncode == 0, (p.stdout[-400:], p.stderr[-1200:])
    assert "REQTRACE_OFF_OK" in p.stdout


def test_scale_off_paths_untouched():
    """tpuscale's off contract (the bench-contract pin): a farm with
    no ScalePolicy never imports the serving.scale package — no
    controller, no planner, no allocator ledger — and the ReplicaGroup
    serve path behaves exactly as the static PR 17 farm (group.scale
    stays None, stats() carries no scale section)."""
    code = (
        "import sys\n"
        "import numpy as np\n"
        "import paddle_tpu as pt\n"
        "from paddle_tpu.core import framework as fw\n"
        "from paddle_tpu.models import transformer as tfm\n"
        "from paddle_tpu.serving.farm import FarmConfig, ReplicaGroup\n"
        "from paddle_tpu.serving.decode import (DecodeConfig,"
        " DecodeEngineConfig)\n"
        "cfg = tfm.TransformerConfig(src_vocab=16, trg_vocab=16,"
        " max_len=8, d_model=8, d_inner=16, n_head=2, n_layer=1,"
        " dropout=0.0, label_smooth_eps=0.0)\n"
        "infer, start = fw.Program(), fw.Program()\n"
        "with pt.program_guard(infer, start):\n"
        "    with pt.unique_name.guard():\n"
        "        tfm.build_infer_program(cfg, maxlen=8)\n"
        "pt.Executor(pt.CPUPlace()).run(start)\n"
        "scope = pt.global_scope()\n"
        "params = {v.name: np.asarray(scope.get(v.name))"
        " for v in infer.persistable_vars()}\n"
        "group = ReplicaGroup(cfg, params, FarmConfig(replicas=2,"
        " engine=DecodeEngineConfig(num_slots=2, max_len=8,"
        " prefill_buckets=(1, 2)),"
        " decode=DecodeConfig(bos=0)), name='static')\n"
        "assert group.scale is None, "
        "'a controller-less group grew a scale hook'\n"
        "fut = group.submit(np.arange(2, 6).astype('int64'),"
        " src_len=4, max_new_tokens=3)\n"
        "for _ in range(60):\n"
        "    if fut.done():\n"
        "        break\n"
        "    group.run_iteration()\n"
        "assert len(fut.result(timeout=0).tokens) == 3\n"
        "assert 'scale' not in group.stats(), "
        "'stats() must not carry a scale section without a controller'\n"
        "assert 'paddle_tpu.serving.scale' not in sys.modules, "
        "'an unconfigured farm imported the scale package'\n"
        "assert 'paddle_tpu.serving.scale.controller' not in"
        " sys.modules\n"
        "assert 'paddle_tpu.serving.scale.planner' not in"
        " sys.modules\n"
        "print('SCALE_OFF_OK')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=240,
                       cwd=REPO)
    assert p.returncode == 0, (p.stdout[-400:], p.stderr[-1200:])
    assert "SCALE_OFF_OK" in p.stdout


def test_memledger_off_paths_untouched():
    """tpumem's off contract (the bench-contract pin): with
    PADDLE_TPU_MEMLEDGER unset, training steps and serving a request
    through the farm never import telemetry.memledger — every seam is
    one bool check — and flipping the ledger on decodes byte-identical
    tokens (measurement must never perturb the measured)."""
    code = (
        "import sys\n"
        "import numpy as np\n"
        "import paddle_tpu as pt\n"
        "from paddle_tpu import telemetry as tm\n"
        "from paddle_tpu.core import framework as fw\n"
        "from paddle_tpu.models import transformer as tfm\n"
        "from paddle_tpu.serving.farm import FarmConfig, ReplicaGroup\n"
        "from paddle_tpu.serving.decode import (DecodeConfig,"
        " DecodeEngineConfig)\n"
        "assert tm.memledger_enabled() is False\n"
        "cfg = tfm.TransformerConfig(src_vocab=16, trg_vocab=16,"
        " max_len=8, d_model=8, d_inner=16, n_head=2, n_layer=1,"
        " dropout=0.0, label_smooth_eps=0.0)\n"
        "infer, start = fw.Program(), fw.Program()\n"
        "with pt.program_guard(infer, start):\n"
        "    with pt.unique_name.guard():\n"
        "        tfm.build_infer_program(cfg, maxlen=8)\n"
        "pt.Executor(pt.CPUPlace()).run(start)\n"
        "scope = pt.global_scope()\n"
        "params = {v.name: np.asarray(scope.get(v.name))"
        " for v in infer.persistable_vars()}\n"
        "group = ReplicaGroup(cfg, params, FarmConfig(replicas=1,"
        " engine=DecodeEngineConfig(num_slots=2, max_len=8,"
        " prefill_buckets=(1, 2)),"
        " decode=DecodeConfig(bos=0)), name='unmetered')\n"
        "def run(rid):\n"
        "    fut = group.submit(np.arange(2, 6).astype('int64'),"
        " src_len=4, max_new_tokens=3, request_id=rid)\n"
        "    for _ in range(60):\n"
        "        if fut.done():\n"
        "            break\n"
        "        group.run_iteration()\n"
        "    return np.asarray(fut.result(timeout=0).tokens,"
        " np.int64)\n"
        "off = run('m-off')\n"
        "assert 'paddle_tpu.telemetry.memledger' not in sys.modules, "
        "'ledger-off serving imported the memory ledger'\n"
        "tm.memledger_enable()\n"
        "on = run('m-on')\n"
        "assert off.tobytes() == on.tobytes(), "
        "'the memory ledger changed the decoded bytes'\n"
        "print('MEMLEDGER_OFF_OK')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PADDLE_TPU_MEMLEDGER", None)
    env.pop("PADDLE_TPU_DEVICE_MEM_CAP", None)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=240,
                       cwd=REPO)
    assert p.returncode == 0, (p.stdout[-400:], p.stderr[-1200:])
    assert "MEMLEDGER_OFF_OK" in p.stdout


def test_sparse_engine_off_paths_untouched():
    """tpusparse's off contract (the bench-contract pin): without a
    distributed table — or with one but no sparse= opt-in — the engine
    module is never imported, the ParallelExecutor compile key stays
    the historical 7-tuple, and the lookup_table kernel's dense gather
    is bit-identical to composing it by hand (no new attrs consumed,
    no dispatch probe on the hot path)."""
    code = (
        "import numpy as np\n"
        "import jax, jax.numpy as jnp\n"
        "import sys\n"
        "import paddle_tpu as pt\n"
        "from paddle_tpu import layers\n"
        "from paddle_tpu.ops.registry import get_kernel, KernelCtx\n"
        "# dense MLP through ParallelExecutor: engine never loads\n"
        "main, startup = pt.Program(), pt.Program()\n"
        "with pt.program_guard(main, startup):\n"
        "    with pt.unique_name.guard():\n"
        "        x = layers.data('x', shape=[8])\n"
        "        y = layers.data('y', shape=[4])\n"
        "        pred = layers.fc(x, size=4)\n"
        "        loss = layers.mean(layers.square_error_cost(pred, y))\n"
        "        pt.optimizer.SGD(0.1).minimize(loss)\n"
        "scope = pt.Scope()\n"
        "rng = np.random.RandomState(0)\n"
        "with pt.scope_guard(scope):\n"
        "    pt.Executor(pt.CPUPlace()).run(startup)\n"
        "    pexe = pt.ParallelExecutor(loss_name=loss.name,\n"
        "                               main_program=main, scope=scope)\n"
        "    pexe.run(feed={'x': rng.randn(8, 8).astype('float32'),\n"
        "                   'y': rng.randn(8, 4).astype('float32')},\n"
        "             fetch_list=[loss])\n"
        "(ckey,) = pexe._cache.keys()\n"
        "assert len(ckey) == 7, ckey\n"
        "assert 'paddle_tpu.parallel.sparse' not in sys.modules, \\\n"
        "    'dense run imported the sparse engine'\n"
        "assert 'paddle_tpu.ops.pallas.embedding' not in sys.modules\n"
        "# the dense lookup_table kernel: bit-identical to the manual\n"
        "# clip+gather composition\n"
        "w = jnp.asarray(rng.randn(32, 8).astype('float32'))\n"
        "ids = jnp.asarray(rng.randint(0, 32, (6, 3, 1)), jnp.int32)\n"
        "out = get_kernel('lookup_table')(KernelCtx(), {'W': [w],\n"
        "    'Ids': [ids]}, {'padding_idx': -1})['Out'][0]\n"
        "ref = jnp.take(w, jnp.clip(jnp.squeeze(ids, -1), 0, 31),\n"
        "               axis=0)\n"
        "assert np.asarray(out).tobytes() == np.asarray(ref).tobytes()\n"
        "assert 'paddle_tpu.parallel.sparse' not in sys.modules\n"
        "print('SPARSE_OFF_OK')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=240,
                       cwd=REPO)
    assert p.returncode == 0, (p.stdout[-400:], p.stderr[-1200:])
    assert "SPARSE_OFF_OK" in p.stdout


def test_async_off_paths_untouched():
    """tpupipe's off contract (the PR-10 bench-contract pin): with
    PADDLE_TPU_ASYNC unset and no async_steps arg, a run never imports
    core.pipeline_exec, the Executor compile key stays the historical
    8-tuple (donating), telemetry stays empty, and the fetch values
    are bit-identical to the raw jitted step-fn composition the
    executor lowers to (same donated persist, same fold_in(seed, step)
    PRNG derivation)."""
    code = (
        "import sys\n"
        "import numpy as np\n"
        "import jax, jax.numpy as jnp\n"
        "import paddle_tpu as pt\n"
        "from paddle_tpu import layers\n"
        "from paddle_tpu import telemetry as tm\n"
        "from paddle_tpu.core.trace import build_step_fn\n"
        "main, startup = pt.Program(), pt.Program()\n"
        "with pt.program_guard(main, startup):\n"
        "    with pt.unique_name.guard():\n"
        "        x = layers.data('x', shape=[8])\n"
        "        y = layers.data('y', shape=[4])\n"
        "        pred = layers.fc(x, size=4)\n"
        "        loss = layers.mean(layers.square_error_cost(pred, y))\n"
        "        pt.optimizer.SGD(0.1).minimize(loss)\n"
        "main.random_seed = startup.random_seed = 6\n"
        "rng = np.random.RandomState(0)\n"
        "feed = {'x': rng.randn(8, 8).astype('float32'),\n"
        "        'y': rng.randn(8, 4).astype('float32')}\n"
        "scope = pt.Scope()\n"
        "with pt.scope_guard(scope):\n"
        "    exe = pt.Executor(pt.CPUPlace())\n"
        "    exe.run(startup)\n"
        "    ref_persist = {v.name: jnp.asarray(np.asarray(\n"
        "        scope.get(v.name))) for v in main.persistable_vars()}\n"
        "    outs = [exe.run(main, feed=feed, fetch_list=[loss])\n"
        "            for _ in range(3)]\n"
        "assert 'paddle_tpu.core.pipeline_exec' not in sys.modules, \\\n"
        "    'sync run imported the async pipeline'\n"
        "ckeys = list(exe._cache)\n"
        "train_keys = [k for k in ckeys if isinstance(k, tuple)\n"
        "              and len(k) == 8]\n"
        "assert len(ckeys) == len(train_keys) == 2, ckeys\n"
        "assert tm.snapshot() == {}\n"
        "# value pin: replay the raw composition the executor lowers\n"
        "# to (startup was executor step 0 -> training steps 1..3)\n"
        "step_fn = build_step_fn(main, [loss.name], False,\n"
        "                        pt.CPUPlace())\n"
        "p = ref_persist\n"
        "vals = []\n"
        "for s in (1, 2, 3):\n"
        "    key = jax.random.fold_in(jax.random.PRNGKey(6),\n"
        "                             jnp.uint32(s))\n"
        "    f, p = jax.jit(step_fn)(p, {k: jnp.asarray(v) for k, v\n"
        "                                in feed.items()}, key)\n"
        "    vals.append(np.asarray(f[0]))\n"
        "for got, want in zip(outs, vals):\n"
        "    assert np.asarray(got[0]).tobytes() == want.tobytes()\n"
        "print('ASYNC_OFF_OK')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PADDLE_TPU_ASYNC", None)
    env.pop("PADDLE_TPU_TELEMETRY", None)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=240,
                       cwd=REPO)
    assert p.returncode == 0, (p.stdout[-400:], p.stderr[-1200:])
    assert "ASYNC_OFF_OK" in p.stdout


def test_attribution_off_paths_untouched():
    """tpuscope's off contract (the PR-12 pin, same pattern as PRs
    9/10/11): with PADDLE_TPU_TELEMETRY unset a training run never
    imports telemetry.attribution or telemetry.slo (no cost_analysis,
    no AOT lowering, no per-ckey registry growth), the Executor compile
    key stays the historical 8-tuple, and the registry snapshot stays
    empty. `import paddle_tpu.telemetry` itself must not pull either
    module in (the lazy __getattr__ contract)."""
    code = (
        "import sys\n"
        "import numpy as np\n"
        "import paddle_tpu as pt\n"
        "from paddle_tpu import layers\n"
        "from paddle_tpu import telemetry as tm\n"
        "img = layers.data('img', shape=[8])\n"
        "out = layers.reduce_mean(layers.fc(img, size=4))\n"
        "exe = pt.Executor(pt.CPUPlace())\n"
        "exe.run(pt.default_startup_program())\n"
        "x = np.random.rand(2, 8).astype('float32')\n"
        "for _ in range(3):\n"
        "    exe.run(feed={'img': x}, fetch_list=[out])\n"
        "assert 'paddle_tpu.telemetry.attribution' not in sys.modules,\\\n"
        "    'telemetry-off run imported the attribution layer'\n"
        "assert 'paddle_tpu.telemetry.slo' not in sys.modules\n"
        "train_keys = [k for k in exe._cache\n"
        "              if isinstance(k, tuple) and len(k) == 8]\n"
        "assert len(train_keys) == len(exe._cache) == 2, \\\n"
        "    list(exe._cache)\n"
        "assert tm.snapshot() == {}\n"
        "assert exe.last_recompile is None\n"
        "print('ATTRIBUTION_OFF_OK')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PADDLE_TPU_TELEMETRY", None)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=240,
                       cwd=REPO)
    assert p.returncode == 0, (p.stdout[-400:], p.stderr[-1200:])
    assert "ATTRIBUTION_OFF_OK" in p.stdout


def test_resilience_off_checkpoint_forward_compatible(tmp_path):
    """save_checkpoint's crash-safe rewrite must stay readable by the
    PRE-PR reader (np.load of params.npz + json.load of
    checkpoint.json — no manifest knowledge), and with all resilience
    env unset a save adds exactly one extra file (the additive
    checksum manifest) next to the two the old writer produced. The
    elastic fields ride the same contract: world_size/layout are
    ADDITIVE manifest keys (an engine-less save records world_size=1,
    grows no shard files, and a pre-elastic checkpoint — no such keys
    at all — still loads with the new reader)."""
    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu import layers
    from paddle_tpu.resilience import checkpoint as rckpt

    img = layers.data("imgfc", shape=[4])
    layers.fc(img, size=3, param_attr=pt.ParamAttr(name="fcw"))
    exe = pt.Executor(pt.CPUPlace())
    exe.run(pt.default_startup_program())
    d = str(tmp_path / "ck")
    meta = pt.io.save_checkpoint(exe, d, step=9)
    assert sorted(os.listdir(d)) == ["checkpoint.json",
                                     "checkpoint.manifest.json",
                                     "params.npz"]
    # the pre-PR reader: direct np.load + json.load, nothing else
    with open(os.path.join(d, "checkpoint.json")) as f:
        old_meta = json.load(f)
    assert old_meta == meta
    with np.load(os.path.join(d, "params.npz"),
                 allow_pickle=False) as data:
        assert "fcw" in data.files
        np.testing.assert_array_equal(
            data["fcw"], np.asarray(pt.global_scope().get("fcw")))
    # elastic fields: additive, logical-world defaults, no layout
    assert meta["world_size"] == 1 and "layout" not in meta
    with open(os.path.join(d, rckpt.MANIFEST_FILE)) as f:
        manifest = json.load(f)
    assert manifest["world_size"] == 1 and "layout" not in manifest
    # vice versa: a PRE-elastic checkpoint (manifest without the new
    # keys, meta without world_size) still loads with the new reader
    d2 = str(tmp_path / "ck_old")
    os.makedirs(d2)
    with np.load(os.path.join(d, "params.npz")) as data:
        np.savez(os.path.join(d2, "params.npz"),
                 **{n: data[n] for n in data.files})
    with open(os.path.join(d2, "checkpoint.json"), "w") as f:
        json.dump({"step": 9, "vars": meta["vars"], "extra": {}}, f)
    rckpt.write_manifest(d2, extra_meta={"step": 9})
    meta2 = pt.io.load_checkpoint(exe, d2)
    assert meta2["step"] == 9 and "world_size" not in meta2


def test_elastic_off_paths_untouched(tmp_path):
    """tpuelastic's off contract (the PR-11 pin, same pattern as PRs
    9/10): a run that never touches a layout-carrying checkpoint never
    imports resilience.elastic — a plain save/load roundtrip stays the
    historical 3-file format with no new imports, and the executor.step
    chaos hook on the ParallelExecutor costs one cached-bool while
    PADDLE_TPU_CHAOS is unset."""
    code = (
        "import sys\n"
        "import numpy as np\n"
        "import paddle_tpu as pt\n"
        "from paddle_tpu import layers\n"
        "from paddle_tpu.resilience import chaos\n"
        "img = layers.data('im', shape=[4])\n"
        "layers.fc(img, size=3)\n"
        "exe = pt.Executor(pt.CPUPlace())\n"
        "exe.run(pt.default_startup_program())\n"
        "meta = pt.io.save_checkpoint(exe, 'ck', step=1)\n"
        "assert meta['world_size'] == 1 and 'layout' not in meta\n"
        "assert pt.io.load_checkpoint(exe, 'ck')['step'] == 1\n"
        "assert 'paddle_tpu.resilience.elastic' not in sys.modules, \\\n"
        "    'an elastic-off checkpoint roundtrip imported elastic'\n"
        "assert chaos.armed() is False and chaos.fired_count() == 0\n"
        "print('ELASTIC_OFF_OK')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("PADDLE_TPU_CHAOS", None)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=240,
                       cwd=str(tmp_path))
    assert p.returncode == 0, (p.stdout[-400:], p.stderr[-1200:])
    assert "ELASTIC_OFF_OK" in p.stdout


def test_kern_default_dispatch_byte_identical():
    """On a backend where no Pallas kernel can run (CPU, auto mode)
    every dispatch rejects at the fn gate and the decode tokens are
    byte-identical to the kernels-off lowering (set_mode("off")) — the
    seam counts evidence, it never changes numerics."""
    code = (
        "import os, sys\n"
        "import numpy as np\n"
        "import paddle_tpu as pt\n"
        "from paddle_tpu.core import framework as fw\n"
        "from paddle_tpu.models import transformer as tfm\n"
        "cfg = tfm.TransformerConfig(src_vocab=32, trg_vocab=32,"
        " max_len=8, d_model=16, d_inner=32, n_head=2, n_layer=1,"
        " dropout=0.0, label_smooth_eps=0.0)\n"
        "infer, start = fw.Program(), fw.Program()\n"
        "with pt.program_guard(infer, start):\n"
        "    with pt.unique_name.guard():\n"
        "        tfm.build_infer_program(cfg, maxlen=8)\n"
        "pt.Executor(pt.CPUPlace()).run(start)\n"
        "scope = pt.global_scope()\n"
        "rng = np.random.RandomState(5)\n"
        "params = {}\n"
        "for v in infer.persistable_vars():\n"
        "    a = np.asarray(scope.get(v.name))\n"
        "    params[v.name] = (0.3 * rng.randn(*a.shape))"
        ".astype(a.dtype)\n"
        "def run():\n"
        "    dec = tfm.IncrementalDecoder(cfg, params, num_slots=2,"
        " max_len=8)\n"
        "    state = dec.init_state()\n"
        "    ids = np.zeros(2, np.int64)\n"
        "    pos = np.zeros(2, np.int64)\n"
        "    toks = []\n"
        "    for _ in range(5):\n"
        "        ids = dec.step(state, ids, pos)\n"
        "        toks.append(ids.copy())\n"
        "        pos = pos + 1\n"
        "    return np.stack(toks)\n"
        "from paddle_tpu.ops.registry import set_mode\n"
        "set_mode('off')\n"
        "off = run()\n"
        "set_mode('auto')\n"
        "on = run()\n"
        "assert off.tobytes() == on.tobytes(), "
        "'auto-mode dispatch changed decode tokens'\n"
        "from paddle_tpu.ops.kern import registry as kreg\n"
        "assert kreg.STATS['dispatches'] > 0, "
        "'decode never consulted the registry'\n"
        "assert kreg.STATS['accepted'] == 0, "
        "'a Pallas kernel claimed to run on the CPU backend'\n"
        "print('KERN_DEFAULT_OK')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=240,
                       cwd=REPO)
    assert p.returncode == 0, (p.stdout[-400:], p.stderr[-1200:])
    assert "KERN_DEFAULT_OK" in p.stdout


def test_telemetry_artifact_helper(tmp_path):
    """bench writes BENCH_telemetry.json iff telemetry is on — the
    helper direct (no 40s bench subprocess): off → None and no file;
    on → a parseable artifact with the snapshot."""
    import importlib.util
    from paddle_tpu import telemetry as tm
    spec = importlib.util.spec_from_file_location("bench_mod", BENCH)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    out = tmp_path / "BENCH_telemetry.json"
    tm.disable()
    tm.reset()
    assert bench._write_telemetry_artifact(str(out)) is None
    assert not out.exists()
    tm.enable()
    try:
        tm.counter("bench.test_metric").inc(7)
        path = bench._write_telemetry_artifact(str(out))
        assert path == str(out)
        obj = json.loads(out.read_text())
        assert obj["schema"] == "paddle_tpu.bench.telemetry.v1"
        assert obj["metrics"]["bench.test_metric"] == 7
    finally:
        tm.disable()
        tm.reset()
