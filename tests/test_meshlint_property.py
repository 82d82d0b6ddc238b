"""Property pin for meshlint's spec checker.

`static_spec_verdict` claims to predict — without tracing — whether
jax.shard_map accepts a (mesh, PartitionSpec, shape) triple. This file holds it to that claim: several hundred randomly
generated configs, each checked against the real shard_map under
`jax.eval_shape`. Any disagreement in either direction is a failure —
a false positive would quarantine working parallel code, a false
negative would let a doomed config reach the compiler.

Seeded RNG, no Hypothesis dependency.
"""
import random

import numpy as np
import pytest

import jax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from paddle_tpu.analysis import meshlint as ml

N_CASES = 320
AXIS_POOL = ("dp", "tp", "pp", "sp")
DIM_POOL = (1, 2, 3, 4, 5, 6, 8, 12)


def _random_mesh(rng):
    """A mesh whose total size divides the 8 virtual CPU devices."""
    n_axes = rng.randint(1, 3)
    while True:
        sizes = [rng.choice((1, 2, 2, 4)) for _ in range(n_axes)]
        total = int(np.prod(sizes))
        if total <= len(jax.devices()):
            break
    names = rng.sample(AXIS_POOL, n_axes)
    devs = np.array(jax.devices()[:total]).reshape(sizes)
    return Mesh(devs, tuple(names)), ml.MeshSpec(
        dict(zip(names, sizes)))


def _random_spec_entry(rng, axes):
    r = rng.random()
    if r < 0.35:
        return None
    if r < 0.45:
        return "zz"  # axis no mesh defines
    if r < 0.85 or len(axes) < 2:
        return rng.choice(axes)
    return tuple(rng.sample(axes, 2))


def _random_case(rng):
    mesh, mspec = _random_mesh(rng)
    ndim = rng.randint(1, 3)
    shape = tuple(rng.choice(DIM_POOL) for _ in range(ndim))
    # mostly legal length; sometimes one entry too many
    spec_len = rng.randint(0, ndim) if rng.random() < 0.9 \
        else ndim + 1
    spec = tuple(_random_spec_entry(rng, list(mesh.axis_names))
                 for _ in range(spec_len))
    return mesh, mspec, spec, shape


def _shard_map_accepts(mesh, spec, shape):
    f = shard_map(lambda x: x, mesh=mesh, in_specs=(P(*spec),),
                  out_specs=P(*spec), check_vma=False)
    try:
        jax.eval_shape(f, jax.ShapeDtypeStruct(shape, np.float32))
        return True
    except Exception:
        return False


def test_spec_verdict_matches_shard_map_behavior():
    rng = random.Random(20260806)
    n_accept = n_reject = 0
    mismatches = []
    for i in range(N_CASES):
        mesh, mspec, spec, shape = _random_case(rng)
        actual = _shard_map_accepts(mesh, spec, shape)
        static, reasons = ml.static_spec_verdict(mspec, spec, shape)
        if actual:
            n_accept += 1
        else:
            n_reject += 1
        if actual != static:
            mismatches.append(
                (dict(mspec.axes), spec, shape, actual, static,
                 reasons))
    assert not mismatches, \
        f"{len(mismatches)}/{N_CASES} disagreements, first 5: " \
        f"{mismatches[:5]}"
    # the sample must genuinely exercise both verdicts
    assert n_accept >= 60, n_accept
    assert n_reject >= 60, n_reject


def test_spec_verdict_reasons_only_on_reject():
    rng = random.Random(7)
    for _ in range(80):
        _, mspec, spec, shape = _random_case(rng)
        ok, reasons = ml.static_spec_verdict(mspec, spec, shape)
        assert ok == (not reasons)


def test_green_parallel_configs_have_zero_errors():
    """The false-positive pin at the config level: every config the
    green parallel tests use must come through
    the FULL pass list with zero error diagnostics."""
    greens = ml.green_configs()
    assert len(greens) >= 5
    for label, mctx in greens:
        errs = [d for d in ml.run_mesh_passes(mctx)
                if d.severity == "error"]
        assert not errs, (label, [d.message for d in errs])
