"""The zero-false-positive control set: the sharded configs of the
repo's passing parallel tests, each reconstructed as a MeshLintContext
— the SAME object the executor gates lint. Every one of them runs
green, so the mesh passes must stay silent on all of them
(tools/tpulint.py checks it, tests/test_meshlint.py pins it).

The configs mirror the tests (meshes, specs, schedules — see
tests/test_four_axis.py, tests/test_pipeline_1f1b.py,
tests/test_parallel_advanced.py, tests/test_multihost.py,
tests/test_parallel.py); keep them in sync when a test changes.
"""
from .context import MeshLintContext, MeshSpec, ShardMapUse

__all__ = ["green_configs"]

_P = ()  # replicated spec


def _gpipe_use(n_stages, data_axis=None):
    """The GPipe PipelineTrainer shard_map call site
    (parallel/pipeline.py:_build_fn): stacked per-stage params sharded
    over pp, feeds replicated (or batch-split over data_axis), loss
    grad taken THROUGH the boundary, body = pipelined lax.scan with
    stage-masked selects + ppermute."""
    feed_spec = (None, data_axis) if data_axis else _P
    n_params = 2 * n_stages  # fc weight + bias per stage
    return ShardMapUse(
        "pipeline.gpipe",
        in_specs=[("pp",)] * n_params + [feed_spec, _P],
        out_specs=[_P])


def _1f1b_use(n_stages, data_axis=None):
    """The 1F1B call site (_build_fn_1f1b): jax.vjp INSIDE the body
    (no boundary transpose), explicit psum of the cond/vjp-masked grad
    accumulator over data_axis when present."""
    feed_spec = (None, data_axis) if data_axis else _P
    n_params = 2 * n_stages
    return ShardMapUse(
        "pipeline.1f1b",
        in_specs=[("pp",)] * n_params + [feed_spec, _P],
        out_specs=[_P] + [("pp",)] * n_params)


def _four_axis_use():
    """four_axis_train_step (parallel/four_axis.py): dp x tp x pp x sp,
    grad through the boundary, pipelined scan over stages."""
    return ShardMapUse(
        "four_axis.train_step",
        in_specs=[("pp", None, "tp"), ("pp", "tp", None),
                  (None, "dp", "sp", None), (None, "dp", "sp", None)],
        out_specs=[_P])


def _multichip_test_configs():
    """[(test_id, MeshLintContext)] for the 18 multichip tests whose
    configs are spelled out test by test."""
    out = []
    four_axis_meshes = [
        ("axes0", {"dp": 2, "tp": 2, "pp": 2, "sp": 1}),
        ("axes1", {"dp": 1, "tp": 2, "pp": 2, "sp": 2}),
        ("axes2", {"dp": 2, "tp": 1, "pp": 2, "sp": 2}),
        ("axes3", {"dp": 1, "tp": 1, "pp": 4, "sp": 2}),
    ]
    for pid, axes in four_axis_meshes:
        out.append((
            f"tests/test_four_axis.py::TestFourAxisLeg::"
            f"test_matches_dense[{pid}]",
            MeshLintContext(MeshSpec(axes), uses=[_four_axis_use()],
                            label=f"four_axis[{pid}]")))
    out.append((
        "tests/test_four_axis.py::TestPipelineWithDataParallel::"
        "test_dp_pp_matches_dense[gpipe]",
        MeshLintContext(MeshSpec({"pp": 2, "dp": 4}),
                        uses=[_gpipe_use(2, data_axis="dp")],
                        pipeline_schedule="gpipe", data_axis="dp",
                        label="dp_pp[gpipe]")))
    out.append((
        "tests/test_four_axis.py::TestPipelineWithDataParallel::"
        "test_dp_pp_matches_dense[1f1b]",
        MeshLintContext(MeshSpec({"pp": 2, "dp": 4}),
                        uses=[_1f1b_use(2, data_axis="dp")],
                        pipeline_schedule="1f1b", data_axis="dp",
                        label="dp_pp[1f1b]")))
    for t in ("test_1f1b_matches_gpipe_and_dense",
              "test_1f1b_matches_gpipe_with_dropout",
              "test_more_microbatches_than_stages"):
        # these compare 1F1B against a GPipe leg
        out.append((
            f"tests/test_pipeline_1f1b.py::TestOneFOneBNumerics::{t}",
            MeshLintContext(MeshSpec({"pp": 4}),
                            uses=[_gpipe_use(4), _1f1b_use(4)],
                            pipeline_schedule="gpipe",
                            label=f"1f1b-vs-gpipe[{t}]")))
    out.append((
        "tests/test_parallel_advanced.py::"
        "test_pipeline_trainer_matches_single_device",
        MeshLintContext(MeshSpec({"pp": 4}), uses=[_gpipe_use(4)],
                        pipeline_schedule="gpipe",
                        label="pipeline_trainer[gpipe]")))
    for t in ("fleet_init_psum", "sharded_checkpoint",
              "data_parallel_training", "ring_attention",
              "pipeline_training", "distributed_table_training",
              "expert_parallel_moe", "tensor_parallel_training"):
        out.append((
            f"tests/test_multihost.py::test_two_process_{t}",
            MeshLintContext(MeshSpec({"dp": 2}),
                            label=f"multihost[{t}]")))
    return out


def green_configs():
    """[(label, MeshLintContext)] for the parallel configs the tests
    run green — the zero-false-positive control set: meshlint must
    produce no ERROR for any of them."""
    out = _multichip_test_configs()
    # pure 1F1B, no data axis: bit-correct (test_1f1b_trains)
    out.append(("1f1b-no-dp", MeshLintContext(
        MeshSpec({"pp": 4}), uses=[_1f1b_use(4)],
        pipeline_schedule="1f1b", label="1f1b-no-dp")))
    # forward-only pipelined scan (pipeline_forward): no boundary grad
    out.append(("pipeline-forward", MeshLintContext(
        MeshSpec({"pp": 4}),
        uses=[ShardMapUse(
            "pipeline.forward",
            in_specs=[("pp",), _P], out_specs=[_P])],
        label="pipeline-forward")))
    # data-parallel gradsync (test_parallel.py): single process
    for mode in ("fp32", "bf16", "int8:bucket_mb=1"):
        out.append((f"gradsync-{mode}", MeshLintContext(
            MeshSpec({"dp": 8}), grad_sync=mode,
            label=f"gradsync[{mode}]")))
    # tensor parallel matmul split (single-process)
    out.append(("tensor-parallel", MeshLintContext(
        MeshSpec({"tp": 4}),
        uses=[ShardMapUse(
            "tp.matmul",
            in_specs=[(None, "tp"), ("tp", None)], out_specs=[_P],
            arg_shapes=[(8, 8), (8, 8)])],
        label="tensor-parallel")))
    # sparse embedding exchange (single-process)
    out.append(("sparse-shard", MeshLintContext(
        MeshSpec({"dp": 8}), grad_sync="fp32", sparse="shard:stale=2",
        label="sparse-shard")))
    return out
