#!/usr/bin/env python
"""tpuchaos — the fault-tolerance layer's CLI and CI gate.

Three jobs, in the tpustat/tpuserve/tpudoctor CLI tradition:

  demo        (default) train a small deterministic model under the
              Guardian, inject faults — a mid-step crash, a torn
              checkpoint write, a transient compile failure, a dead
              rank — and show the resilience layer surviving each:
              auto-resume from the last valid checkpoint, torn-write
              candidates skipped, retries absorbed, liveness flagged.
  worker      (internal) one deterministic Guardian training run in a
              subprocess — the kill -9 target. Faults come from
              PADDLE_TPU_CHAOS in the environment; on completion a
              result JSON (final loss, restarts) is written, so the
              parent can verify an interrupted-then-resumed job
              reaches the same loss as an uninterrupted one.
  elastic-worker
              (internal) one phase of the elastic selftest: a
              Guardian-supervised sparse-embedding training run over a
              --world-member mesh (first W of the 8 virtual CPU
              devices), resuming from whatever topology-independent
              checkpoint the root holds — written at ANY world size.
              PADDLE_TPU_CHAOS decides whether a rank is lost (SIGKILL)
              or a resize request arrives (exit 17 + resize.json).
  --selftest-elastic
              the elastic CI gate (ROADMAP item 4): N=8 training loses
              rank 3 to a SIGKILL mid-step; the coordinator detects the
              silence via liveness, re-forms at N=6, and the run resumes
              from the world-8 checkpoint through the streaming
              r%8 -> r%6 shard shuffle; a resize request then grows the
              fleet back to N=8 (r%6 -> r%8). Asserts the final loss is
              within tolerance of an uninterrupted N=8 run and that the
              per-row embedding fingerprints survive BOTH shuffles
              byte-for-byte (zero lost rows).
  --selftest  CI gate: all demo legs with assertions —
              (1) a run killed mid-step (in-process fault AND a real
                  SIGKILL'd subprocess) auto-resumes from the last
                  valid checkpoint and reaches a final loss within
                  tolerance of the uninterrupted run;
              (2) a checkpoint write torn at ANY injected byte offset
                  never leaves the root without a loadable restore
                  point (rotation GC keeps the last valid one);
              (3) transient compile faults are absorbed by the retry
                  engine (resilience.retry.* counters);
              (4) a silent rank turns into a typed FleetFault via the
                  spool-heartbeat liveness detector.
              One JSON verdict line with --json; exit 2 on any
              problem.

Examples:
  python tools/tpuchaos.py                         # demo
  python tools/tpuchaos.py --selftest --json       # CI gate
  PADDLE_TPU_CHAOS="step_fail:at=9,mode=kill" \\
      python tools/tpuchaos.py worker --root /tmp/ckpt --steps 12
"""
import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

STEPS = 12
SAVE_EVERY = 4
# chaos executor.step hit N: startup run is hit 1, training step k is
# hit k+2 -> at=9 crashes step 7, after the step-3 checkpoint landed
CRASH_AT = 9
LOSS_RTOL = 1e-4

# ---- elastic selftest rig (N=8 -> 6 -> 8, ROADMAP item 4) ----------
E_VOCAB = 50          # % 8 != 0 and % 6 != 0: pad rows exercised
E_DIM = 8
E_BATCH = 24          # divisible by every world in E_CHOICES
E_FIELDS = 4
E_STEPS = 12
E_SAVE_EVERY = 3
E_CHOICES = (8, 6, 4, 2)
# phase A (world 8): startup hit 1, step k is hit k+2 -> at=9 kills
# step 7, after the step-5 checkpoint (done=6) landed -> resume at 6
E_KILL_AT = 9
# phase B (world 6) resumes at step 6: startup hit 1, step 6+k is hit
# k+2 -> at=6 fires the resize at step 10, after the step-8 checkpoint
E_RESIZE_AT = 6
# loss reassociation across world sizes (pmean of 3-member means vs
# 4-member means) is ~1e-7/step; 1e-3 leaves SGD drift headroom
E_LOSS_RTOL = 1e-3
EXIT_RESIZE = 17      # elastic-worker: "re-form me at resize.json:to"


# ------------------------------------------------------- training rig

def _build_model():
    import paddle_tpu as pt
    from paddle_tpu import layers
    main_p, startup_p = pt.Program(), pt.Program()
    with pt.program_guard(main_p, startup_p):
        with pt.unique_name.guard():
            x = layers.data("x", shape=[8])
            y = layers.data("y", shape=[1])
            h = layers.fc(x, 16, act="tanh")
            pred = layers.fc(h, 1)
            loss = layers.reduce_mean(
                layers.square_error_cost(pred, y))
            opt = pt.optimizer.Adam(1e-2)
            opt.minimize(loss)
    return main_p, startup_p, loss


def _feed_for_step(step):
    """Pure function of the step index — resumption replays the exact
    stream an uninterrupted run would have seen (the Guardian
    determinism contract)."""
    import numpy as np
    rng = np.random.RandomState(1000 + step)
    x = rng.rand(16, 8).astype("float32")
    y = (0.5 * x.sum(axis=1, keepdims=True)).astype("float32")
    return {"x": x, "y": y}


def _train_with_guardian(root, steps=STEPS, max_restarts=3):
    """One Guardian-supervised run in a fresh scope. Returns
    (final_loss, guardian)."""
    import paddle_tpu as pt
    from paddle_tpu.resilience import Guardian

    main_p, startup_p, loss = _build_model()
    exe = pt.Executor(pt.CPUPlace())
    scope = pt.Scope()
    with pt.scope_guard(scope):
        guardian = Guardian(exe, main_p, root,
                            startup_program=startup_p,
                            save_every=SAVE_EVERY,
                            max_restarts=max_restarts)

        def step_fn(step):
            out = exe.run(main_p, feed=_feed_for_step(step),
                          fetch_list=[loss])
            return float(out[0])

        final = guardian.run_with_recovery(step_fn, steps)
    return final, guardian


# ------------------------------------------------------------- worker

def cmd_worker(args):
    """Subprocess target: PADDLE_TPU_CHAOS in the env decides whether
    this run dies; a completed run writes the result JSON."""
    final, guardian = _train_with_guardian(args.root, steps=args.steps)
    result = {"final_loss": final, "steps": args.steps,
              "restarts": guardian.restarts,
              "restores": guardian.restore_count}
    path = args.result or os.path.join(args.root, "result.json")
    with open(path, "w") as f:
        json.dump(result, f)
    print(json.dumps(result))
    return 0


# ---------------------------------------------------- elastic worker

def _build_elastic_model(seed=17):
    """Sparse-embedding model for the elastic rig: a mod-sharded
    distributed table under the tpusparse engine — the state whose
    r%N -> r%M shuffle the selftest audits row by row."""
    import paddle_tpu as pt
    from paddle_tpu import layers
    main_p, startup_p = pt.Program(), pt.Program()
    with pt.program_guard(main_p, startup_p):
        with pt.unique_name.guard():
            ids = layers.data("ids", shape=[E_FIELDS, 1], dtype="int64")
            y = layers.data("y", shape=[E_DIM], dtype="float32")
            emb = layers.embedding(
                ids, size=[E_VOCAB, E_DIM], is_sparse=True,
                is_distributed=True,
                param_attr=pt.ParamAttr(name="etbl"))
            loss = layers.reduce_mean(layers.square_error_cost(
                layers.reduce_sum(emb, dim=1), y))
            pt.optimizer.SGD(0.1).minimize(loss)
    main_p.random_seed = startup_p.random_seed = seed
    return main_p, startup_p, loss


def _elastic_feed(step):
    """Pure function of the step index (the Guardian determinism
    contract) with a GLOBAL batch divisible by every world size in
    E_CHOICES — resumption at any N replays the same stream."""
    import numpy as np
    rng = np.random.RandomState(7000 + step)
    ids = rng.randint(0, E_VOCAB,
                      (E_BATCH, E_FIELDS, 1)).astype("int64")
    y = rng.randn(E_BATCH, E_DIM).astype("float32")
    return {"ids": ids, "y": y}


def cmd_elastic_worker(args):
    """One phase of the elastic run: Guardian-supervised training over
    a --world-member mesh, resumed from whatever topology-independent
    checkpoint --root holds (written at ANY world size — the restore
    streams the r%N -> r%M shuffle). A rank_lost:mode=kill fault dies
    mid-step; a resize fault exits EXIT_RESIZE with resize.json so the
    coordinator re-forms at the requested size."""
    import numpy as np
    import jax
    import paddle_tpu as pt
    from paddle_tpu.parallel.mesh import local_mesh
    from paddle_tpu.resilience import Guardian, chaos
    from paddle_tpu.resilience import elastic

    world = args.world
    main_p, startup_p, loss = _build_elastic_model()
    exe = pt.Executor(pt.CPUPlace())
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe.run(startup_p)
        mesh = local_mesh("dp", devices=jax.devices()[:world])
        pexe = pt.ParallelExecutor(loss_name=loss.name,
                                   main_program=main_p, scope=scope,
                                   mesh=mesh, sparse="shard")
        guardian = Guardian(pexe, main_p, args.root,
                            save_every=E_SAVE_EVERY, max_restarts=3)

        def logical_tables():
            eng = pexe.sparse_engine
            return {name: eng.to_logical(eng.owner_table(name),
                                         np.asarray(scope.get(name)))
                    for name in eng.row_var_names
                    if scope.get(name) is not None}

        if args.dump_restore:
            # audit hook: restore NOW and fingerprint the re-sharded
            # rows before any training step touches them — the parent
            # compares these against the checkpoint's own fingerprints
            # (zero-lost-rows). run_with_recovery restores again
            # (idempotent) below.
            resumed = guardian.restore()
            fps = {n: [int(x) for x in elastic.fingerprint_array(a)]
                   for n, a in logical_tables().items()}
            with open(args.dump_restore, "w") as f:
                json.dump({"resume_at": resumed, "world": world,
                           "fingerprints": fps}, f)

        def step_fn(step):
            out = pexe.run(feed=_elastic_feed(step), fetch_list=[loss])
            return float(np.asarray(out[0]))

        try:
            final = guardian.run_with_recovery(step_fn, args.steps)
        except chaos.ResizeFault as e:
            # a planned grow/shrink: hand the requested size back to
            # the coordinator; the last periodic checkpoint is the
            # resume point (deterministic feeds replay the gap)
            with open(os.path.join(args.root, "resize.json"), "w") as f:
                json.dump({"to": e.to, "world": world}, f)
            return EXIT_RESIZE
        table = logical_tables()["etbl"]
    result = {"final_loss": final, "steps": args.steps, "world": world,
              "restarts": guardian.restarts,
              "table": np.asarray(table, dtype=float).tolist()}
    path = args.result or os.path.join(args.root, "result.json")
    with open(path, "w") as f:
        json.dump(result, f)
    print(json.dumps({"final_loss": final, "world": world}))
    return 0


# ---------------------------------------------------------- demo legs

def run_demo(selftest=False):
    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu import telemetry as tm
    from paddle_tpu.io import CheckpointSaver, latest_checkpoint
    from paddle_tpu.resilience import (FleetFault, chaos, checkpoint,
                                       liveness)

    problems = []
    info = {}

    def check(ok, what):
        if not ok:
            problems.append(what)
        return ok

    def say(msg):
        if not selftest:
            print(msg)

    chaos.reset()
    check(not chaos.armed(), "chaos armed with no spec configured")

    # 1) baseline: uninterrupted run ---------------------------------
    base_root = tempfile.mkdtemp(prefix="tpuchaos_base_")
    base_loss, g0 = _train_with_guardian(base_root)
    info["baseline_loss"] = base_loss
    say(f"[baseline] {STEPS} uninterrupted steps, final loss "
        f"{base_loss:.6f}")
    check(g0.restarts == 0, "baseline run restarted")
    check(latest_checkpoint(base_root) is not None,
          "baseline run left no checkpoint")

    # 2) in-process crash at step 7 → Guardian auto-resume ------------
    crash_root = tempfile.mkdtemp(prefix="tpuchaos_crash_")
    chaos.configure(f"step_fail:at={CRASH_AT}")
    try:
        crash_loss, g1 = _train_with_guardian(crash_root)
    finally:
        chaos.reset()
    info["crash_resume_loss"] = crash_loss
    info["crash_restarts"] = g1.restarts
    say(f"[crash]    injected ChaosFault at step {CRASH_AT - 2}; "
        f"guardian restarted {g1.restarts}x, resumed from the last "
        f"valid checkpoint, final loss {crash_loss:.6f}")
    check(g1.restarts == 1, f"expected 1 restart, got {g1.restarts}")
    check(np.isclose(crash_loss, base_loss, rtol=LOSS_RTOL),
          f"crash-resumed loss {crash_loss} != baseline {base_loss}")

    # 3) kill -9 mid-step in a real subprocess → fresh-process resume -
    kill_root = tempfile.mkdtemp(prefix="tpuchaos_kill_")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PADDLE_TPU_CHAOS=f"step_fail:at={CRASH_AT},mode=kill")
    env.pop("PADDLE_TPU_TELEMETRY", None)
    cmd = [sys.executable, os.path.abspath(__file__), "worker",
           "--root", kill_root, "--steps", str(STEPS)]
    p1 = subprocess.run(cmd, env=env, capture_output=True, text=True,
                        timeout=300)
    check(p1.returncode == -signal.SIGKILL,
          f"worker exited {p1.returncode}, wanted -SIGKILL: "
          f"{p1.stderr[-300:]}")
    check(latest_checkpoint(kill_root) is not None,
          "killed worker left no valid checkpoint")
    env.pop("PADDLE_TPU_CHAOS")
    p2 = subprocess.run(cmd, env=env, capture_output=True, text=True,
                        timeout=300)
    check(p2.returncode == 0,
          f"resume worker failed rc={p2.returncode}: "
          f"{p2.stderr[-300:]}")
    kill_loss = None
    try:
        with open(os.path.join(kill_root, "result.json")) as f:
            kill_loss = json.load(f)["final_loss"]
    except (OSError, ValueError, KeyError):
        problems.append("resumed worker wrote no result.json")
    info["kill9_resume_loss"] = kill_loss
    if kill_loss is not None:
        say(f"[kill -9]  subprocess SIGKILL'd mid-step, fresh process "
            f"auto-resumed, final loss {kill_loss:.6f}")
        check(np.isclose(kill_loss, base_loss, rtol=LOSS_RTOL),
              f"kill-9 resumed loss {kill_loss} != baseline "
              f"{base_loss}")

    # 4) torn-write sweep: never without a loadable restore point ----
    torn_root = tempfile.mkdtemp(prefix="tpuchaos_torn_")
    main_p, startup_p, _loss = _build_model()
    exe = pt.Executor(pt.CPUPlace())
    scope = pt.Scope()
    with pt.scope_guard(scope):
        exe.run(startup_p)
        saver = CheckpointSaver(torn_root, max_to_keep=2,
                                async_save=False)
        saver.save(exe, main_p, step=0)
        first = latest_checkpoint(torn_root)
        check(first is not None, "seed checkpoint invalid")
        psize = os.path.getsize(os.path.join(first, "params.npz"))
        offsets = sorted({0, 1, 37, psize // 3, psize // 2,
                          psize - 1, psize})
        torn_ok = 0
        for i, byte in enumerate(offsets):
            chaos.configure(f"ckpt_torn:byte={byte}")
            try:
                saver.save(exe, main_p, step=i + 1)
                problems.append(
                    f"torn write at byte {byte} did not surface")
            except RuntimeError:
                pass
            finally:
                chaos.reset()
            latest = latest_checkpoint(torn_root)
            if latest is None or not checkpoint.is_valid(latest):
                problems.append(
                    f"torn write at byte {byte} left no valid "
                    "restore point")
                continue
            torn_ok += 1
        # and the root still LOADS after the whole sweep
        try:
            meta = pt.io.load_checkpoint(exe, torn_root, main_p)
            check(meta["step"] == 0, "restored wrong checkpoint")
        except Exception as e:
            problems.append(f"post-sweep load failed: {e}")
    info["torn_offsets_survived"] = f"{torn_ok}/{len(offsets)}"
    say(f"[torn]     checkpoint writes torn at byte offsets "
        f"{offsets}: root kept a loadable restore point every time")

    # 5) transient compile faults absorbed by the retry engine -------
    import numpy as np  # noqa: F811 (readability in this long fn)
    retry_dir = tempfile.mkdtemp(prefix="tpuchaos_retry_")
    from paddle_tpu import layers
    from paddle_tpu.inference import InferenceEngine
    inf_main, inf_start = pt.Program(), pt.Program()
    with pt.program_guard(inf_main, inf_start):
        with pt.unique_name.guard():
            xv = layers.data("xv", shape=[4])
            pv = layers.fc(xv, 2, act="softmax")
    exe2 = pt.Executor(pt.CPUPlace())
    scope2 = pt.Scope()
    with pt.scope_guard(scope2):
        exe2.run(inf_start)
        pt.io.save_inference_model(retry_dir, ["xv"], [pv], exe2,
                                   main_program=inf_main)
    tm.enable()
    tm.reset()
    chaos.configure("compile_fail:at=1,times=2")
    try:
        eng = InferenceEngine.from_dir(retry_dir)
        out = eng.run({"xv": np.zeros((2, 4), "float32")})
        snap = tm.snapshot()
    finally:
        chaos.reset()
        tm.disable()
        tm.reset()
    check(len(out) == 1 and out[0].shape == (2, 2),
          "inference under injected compile faults returned garbage")
    retries = snap.get("resilience.retry.retries", 0)
    info["compile_retries"] = retries
    say(f"[retry]    2 injected transient compile failures absorbed "
        f"({retries} retries, then success)")
    check(retries == 2, f"expected 2 retries, counters say {retries}")

    # 6) dead-rank detection on a stale spool ------------------------
    import time
    spool = tempfile.mkdtemp(prefix="tpuchaos_spool_")
    now = time.time()
    for rank, age in ((0, 1.0), (1, 600.0)):
        path = os.path.join(spool, f"rank{rank:05d}.snap.json")
        with open(path, "w") as f:
            json.dump({"schema": "paddle_tpu.fleet.snapshot.v1",
                       "rank": rank,
                       "flush_unix_us": int((now - age) * 1e6),
                       "metrics": {}}, f)
        os.utime(path, (now - age, now - age))
    report = liveness.check_liveness(spool, stale_after_s=60.0,
                                     expected_world=3)
    info["liveness"] = report["verdict"]
    say(f"[liveness] {report['verdict']}")
    check(report["dead"] == [1], f"dead ranks {report['dead']} != [1]")
    check(report["missing"] == [2],
          f"missing ranks {report['missing']} != [2]")
    try:
        liveness.assert_alive(spool, stale_after_s=60.0,
                              expected_world=3)
        problems.append("assert_alive did not raise on a dead rank")
    except FleetFault as e:
        check(1 in e.ranks, "FleetFault does not name the dead rank")

    return problems, info


# ------------------------------------------------------- elastic legs

def _ckpt_fingerprints(path):
    """(fingerprints, world_size) straight from a checkpoint's shard
    files — per logical row, streamed shard by shard (the parent-side
    half of the zero-lost-rows audit)."""
    from paddle_tpu.resilience import elastic
    with open(os.path.join(path, "checkpoint.json")) as f:
        meta = json.load(f)
    fps = {}
    for name, rec in sorted(meta.get("layout", {}).items()):
        read = elastic.read_shard_fn(path, rec)
        fps[name] = [int(x) for x in elastic.fingerprint_rows(
            read, rec["world"], rec["vocab"])]
    return fps, meta.get("world_size")


def run_elastic_demo(selftest=False):
    """The N=8 -> 6 -> 8 gate: rank loss, liveness detection, shrink,
    resize request, grow — every transition through the topology-
    independent checkpoint, with loss-tolerance and per-row-fingerprint
    assertions."""
    import time

    import numpy as np
    from paddle_tpu.io import latest_checkpoint
    from paddle_tpu.resilience import elastic, liveness

    problems = []
    info = {}

    def check(ok, what):
        if not ok:
            problems.append(what)
        return ok

    def say(msg):
        if not selftest:
            print(msg)

    base_root = tempfile.mkdtemp(prefix="tpuelastic_base_")
    run_root = tempfile.mkdtemp(prefix="tpuelastic_run_")
    spool = os.path.join(run_root, "spool")

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PADDLE_TPU_TELEMETRY="1",
               PADDLE_TPU_FLEET_RANK="0",
               PADDLE_TPU_FLEET_WORLD="1",
               PADDLE_TPU_FLEET_DIR=spool,
               PADDLE_TPU_FLEET_FLUSH_S="0.05")
    flags = env.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    env.pop("PADDLE_TPU_CHAOS", None)

    def worker(world, root, chaos_spec=None, dump=None):
        e = dict(env)
        if chaos_spec:
            e["PADDLE_TPU_CHAOS"] = chaos_spec
        cmd = [sys.executable, os.path.abspath(__file__),
               "elastic-worker", "--root", root, "--world", str(world),
               "--steps", str(E_STEPS)]
        if dump:
            cmd += ["--dump-restore", dump]
        return subprocess.run(cmd, env=e, capture_output=True,
                              text=True, timeout=300)

    # [baseline] uninterrupted N=8 run ------------------------------
    p = worker(8, base_root)
    check(p.returncode == 0,
          f"elastic baseline failed rc={p.returncode}: "
          f"{p.stderr[-400:]}")
    base = {}
    try:
        with open(os.path.join(base_root, "result.json")) as f:
            base = json.load(f)
    except (OSError, ValueError):
        problems.append("elastic baseline wrote no result.json")
    info["elastic_baseline_loss"] = base.get("final_loss")
    say(f"[baseline] {E_STEPS} uninterrupted steps at N=8, final loss "
        f"{base.get('final_loss', float('nan')):.6f}")

    coordinator = elastic.ElasticCoordinator(run_root, world=8,
                                             choices=E_CHOICES)

    # [phase A] rank 3 preempted (real SIGKILL) at N=8 ---------------
    p = worker(8, run_root,
               chaos_spec=f"rank_lost:rank=3,at={E_KILL_AT},mode=kill")
    check(p.returncode == -signal.SIGKILL,
          f"rank_lost worker exited {p.returncode}, wanted -SIGKILL: "
          f"{p.stderr[-400:]}")
    # the dead worker's heartbeat goes stale -> liveness turns the
    # silence into a typed report BEFORE anything hangs on it
    time.sleep(1.0)
    report = liveness.check_liveness(spool, stale_after_s=0.5,
                                     expected_ranks=[0])
    check(not report["ok"],
          "liveness did not flag the SIGKILL'd worker's stale spool")
    ck8 = latest_checkpoint(run_root)
    check(ck8 is not None, "killed run left no valid checkpoint")
    fps8, world8 = _ckpt_fingerprints(ck8) if ck8 else ({}, None)
    check(world8 == 8, f"checkpoint world_size {world8} != 8")
    plan = coordinator.plan_after_loss([3])
    check(plan.new_world == 6,
          f"plan after 1 lost rank chose {plan.new_world}, wanted 6 "
          f"(choices {E_CHOICES})")
    coordinator.reform(plan)
    say(f"[rank lost] rank 3 SIGKILL'd at N=8 step {E_KILL_AT - 2}; "
        f"liveness: {report['verdict']}; plan: {plan.reason}")

    # [phase B] resume at N=6; a grow request arrives mid-run --------
    dump6 = os.path.join(run_root, "dump6.json")
    p = worker(coordinator.world, run_root,
               chaos_spec=f"resize:to=8,at={E_RESIZE_AT}", dump=dump6)
    check(p.returncode == EXIT_RESIZE,
          f"resize worker exited {p.returncode}, wanted {EXIT_RESIZE}: "
          f"{p.stderr[-400:]}")
    d6 = {}
    try:
        with open(dump6) as f:
            d6 = json.load(f)
    except (OSError, ValueError):
        problems.append("N=6 worker wrote no restore dump")
    check(d6.get("resume_at") not in (None, 0),
          f"N=6 run did not resume from the N=8 checkpoint "
          f"(resume_at={d6.get('resume_at')})")
    check(d6.get("fingerprints") == fps8,
          "embedding rows lost/changed in the r%8 -> r%6 shuffle")
    ck6 = latest_checkpoint(run_root)
    fps6, world6 = _ckpt_fingerprints(ck6) if ck6 else ({}, None)
    check(world6 == 6, f"post-shrink checkpoint world_size {world6}")
    try:
        with open(os.path.join(run_root, "resize.json")) as f:
            resize_to = json.load(f)["to"]
    except (OSError, ValueError, KeyError):
        resize_to = 8
        problems.append("resize worker wrote no resize.json")
    coordinator.reform(coordinator.plan_resize(resize_to))
    say(f"[shrink]   resumed at N=6 from step {d6.get('resume_at')} "
        f"(rows intact); resize request -> grow back to {resize_to}")

    # [phase C] back at N=8, run to completion -----------------------
    dump8 = os.path.join(run_root, "dump8.json")
    p = worker(coordinator.world, run_root, dump=dump8)
    check(p.returncode == 0,
          f"grow-back worker failed rc={p.returncode}: "
          f"{p.stderr[-400:]}")
    d8 = {}
    try:
        with open(dump8) as f:
            d8 = json.load(f)
    except (OSError, ValueError):
        problems.append("N=8 grow-back worker wrote no restore dump")
    check(d8.get("fingerprints") == fps6,
          "embedding rows lost/changed in the r%6 -> r%8 shuffle")
    res = {}
    try:
        with open(os.path.join(run_root, "result.json")) as f:
            res = json.load(f)
    except (OSError, ValueError):
        problems.append("elastic run wrote no final result.json")
    info["elastic_final_loss"] = res.get("final_loss")
    info["elastic_worlds"] = coordinator.history
    if res.get("final_loss") is not None and \
            base.get("final_loss") is not None:
        check(np.isclose(res["final_loss"], base["final_loss"],
                         rtol=E_LOSS_RTOL),
              f"elastic final loss {res['final_loss']} vs baseline "
              f"{base['final_loss']} outside rtol={E_LOSS_RTOL}")
        check(np.allclose(np.asarray(res.get("table", [])),
                          np.asarray(base.get("table", [])),
                          rtol=1e-2, atol=1e-4),
              "final embedding table diverged from the uninterrupted "
              "run beyond tolerance")
    say(f"[grow]     resumed at N=8 from step {d8.get('resume_at')}, "
        f"final loss {res.get('final_loss', float('nan')):.6f} "
        f"(baseline {base.get('final_loss', float('nan')):.6f}); "
        f"world history {coordinator.history}")
    return problems, info


# ---------------------------------------------------------------- main

def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("command", nargs="?", default="demo",
                   choices=["demo", "worker", "elastic-worker"])
    p.add_argument("--root", default=None,
                   help="checkpoint root (worker)")
    p.add_argument("--steps", type=int, default=STEPS)
    p.add_argument("--result", default=None,
                   help="result JSON path (worker; default "
                        "<root>/result.json)")
    p.add_argument("--world", type=int, default=8,
                   help="mesh size (elastic-worker): first W of the "
                        "local devices")
    p.add_argument("--dump-restore", default=None,
                   help="elastic-worker: restore immediately and dump "
                        "resume step + per-row table fingerprints to "
                        "this JSON before training (the zero-lost-rows "
                        "audit)")
    p.add_argument("--selftest", action="store_true",
                   help="run the CI gate assertions")
    p.add_argument("--selftest-elastic", action="store_true",
                   dest="selftest_elastic",
                   help="run the elastic N=8 -> 6 -> 8 gate")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="one machine-readable JSON verdict line")
    p.add_argument("--platform", default="env",
                   help="JAX_PLATFORMS to force before backend init "
                        "(default 'env': keep the environment's)")
    args = p.parse_args(argv)

    if args.platform != "env":
        os.environ["JAX_PLATFORMS"] = args.platform
    if args.command == "elastic-worker" or args.selftest_elastic:
        # the elastic rig simulates the mesh with 8 virtual CPU
        # devices (tests/conftest.py's trick) — must land before the
        # first jax import, which all happen inside the commands
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()

    if args.command == "worker":
        if not args.root:
            p.error("worker needs --root")
        return cmd_worker(args)
    if args.command == "elastic-worker":
        if not args.root:
            p.error("elastic-worker needs --root")
        return cmd_elastic_worker(args)

    if args.selftest_elastic:
        problems, info = run_elastic_demo(
            selftest=args.selftest or args.as_json)
        result = {"ok": not problems, "problems": problems}
        result.update(info)
        if args.as_json:
            print(json.dumps(result, default=str))
        elif problems:
            for prob in problems:
                print(f"PROBLEM: {prob}", file=sys.stderr)
        else:
            print("tpuchaos elastic: all checks passed "
                  f"(worlds {info['elastic_worlds']}, baseline "
                  f"{info['elastic_baseline_loss']:.6f} ~= elastic "
                  f"{info['elastic_final_loss']:.6f}, zero lost rows)")
        return 2 if problems else 0

    problems, info = run_demo(selftest=args.selftest)
    result = {"ok": not problems, "problems": problems}
    result.update(info)
    if args.as_json:
        print(json.dumps(result, default=str))
    else:
        if problems:
            for prob in problems:
                print(f"PROBLEM: {prob}", file=sys.stderr)
        else:
            print("tpuchaos: all checks passed "
                  f"(baseline {info['baseline_loss']:.6f} == "
                  f"crash-resume {info['crash_resume_loss']:.6f} == "
                  f"kill-9-resume {info['kill9_resume_loss']:.6f})")
    return 2 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
