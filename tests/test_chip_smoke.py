"""chip_smoke.py's plumbing, without a chip: the same phase functions
the chip run calls, at TransformerConfig.tiny() on the CPU backend with
the Pallas kernels in interpret mode — and the script itself refusing
to run where JAX finds no TPU."""
import importlib.util
import os
import subprocess
import sys

import pytest

import paddle_tpu as fluid
from paddle_tpu.models.transformer import TransformerConfig
from paddle_tpu.ops.pallas import flash_attention as fa

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def watch(smoke):
    w = smoke.CompileWatch()
    yield w
    w.close()


@pytest.fixture
def interpret():
    fa.set_mode("interpret")
    yield
    fa.set_mode("auto")


def _tiny():
    cfg = TransformerConfig.tiny()
    cfg.fused_qkv = True
    return cfg


def test_train_then_serve_phases(smoke, watch, interpret):
    cfg = _tiny()
    place = fluid.CPUPlace()
    scope, _main_p, info = smoke.phase_train(
        cfg, batch=8, seq=cfg.max_len, steps=5, place=place,
        watch=watch, kernels="interpret")
    assert info["loss_last"] < info["loss_first"]
    assert info["layer_norm_pallas_calls"] > 0
    assert info["peak_bytes_in_use"] is None      # CPU reports none
    served = smoke.phase_serve(
        cfg, scope, bf16=True, place=place, watch=watch, num_slots=4,
        max_len=cfg.max_len,
        requests=[(3, 5), (9, 3), (17, 8), (31, 2), (5, 6), (12, 4),
                  (20, 7), (2, 9)],
        logits_tol=0.05)
    assert served["requests"] == 9
    assert served["executables"] == 4             # buckets 1,2,4 + step


def test_kernels_phase_runs_every_registered_kernel(smoke, watch,
                                                    interpret):
    info = smoke.phase_kernels(smoke.example_kernel_cases(), watch)
    assert sorted(info) == ["decode_attend", "dequant_attend_int8",
                            "flash_attention", "int8_quant",
                            "kda_attention", "layer_norm", "lookup_pool",
                            "moe_expert_ffn", "selective_scan"]


def test_kernels_phase_fails_on_a_rejected_shape(smoke, watch):
    """Mode "auto" on the CPU backend: every kernel's gate says no
    (ok is None), which the smoke counts as a failure."""
    with pytest.raises(smoke.SmokeFailure, match="parity None"):
        smoke.phase_kernels(smoke.example_kernel_cases(), watch)


def test_chip_kernel_cases_pass_their_hardware_gates(smoke):
    """The shapes the chip run uses are ones each kernel's own
    hardware gate (interpret=False) accepts."""
    from paddle_tpu.ops import kern
    cases = smoke.chip_kernel_cases()
    assert sorted({c.split("@")[0] for c in cases}) == kern.names()
    assert {"flash_attention@head128", "flash_attention@window",
            "kda_attention"} <= set(cases)
    for name, (args, kwargs, _argnums) in cases.items():
        assert kern.get(name.split("@")[0]).probe(*args, **kwargs), name
    q, k, v = cases["flash_attention@head128"][0]
    assert (q.shape, k.shape) == ((1, 8, 8192, 128), (1, 1, 8192, 128))
    assert cases["kda_attention"][0][0].shape == (1, 8192, 8, 128)
    args, kwargs, argnums = cases["flash_attention@window"]
    assert kwargs == {"causal": True, "window": 1024} and argnums == (0, 1, 2)
    assert args[0].shape == (1, 8, 8192, 128)


def test_dp_phase_over_the_virtual_mesh(smoke, watch, interpret):
    cfg = _tiny()
    _scope, _main_p, one = smoke.phase_train(
        cfg, batch=8, seq=cfg.max_len, steps=2, place=fluid.CPUPlace(),
        watch=watch, kernels="interpret")
    info = smoke.phase_dp(cfg, batch=8, seq=cfg.max_len, steps=3,
                          first_loss_one_chip=one["loss_first"],
                          loss_rtol=0.02, watch=watch)
    assert info["devices"] == 8


def test_result_line_has_exactly_the_contract_keys(smoke):
    """The driver reads the last stdout line: `ok` and `device`
    (`platform`, `kind`, `count`) and nothing else."""
    import json

    import jax
    dev = jax.devices()[0]
    line = smoke.result_line(dev, len(jax.devices()))
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())}}


def test_script_refuses_to_run_without_a_tpu():
    """`JAX_PLATFORMS=cpu python chip_smoke.py`: non-zero exit naming
    the platform it found, and no result line."""
    p = subprocess.run([sys.executable, SMOKE],
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=240,
                       cwd=REPO)
    assert p.returncode != 0
    assert "'cpu'" in p.stderr and "not a TPU" in p.stderr
    assert "platform=cpu" in p.stdout
    assert '"ok"' not in p.stdout
