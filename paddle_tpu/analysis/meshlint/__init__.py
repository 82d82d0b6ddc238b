"""paddle_tpu.analysis.meshlint — parallel-aware static verifier.

proglint (analysis/passes.py) stops at the single-device boundary: it
checks one Program against one abstract device. Every parallel
subsystem stacked on top of it — shard_map call sites, gradsync
policies, the sparse engine, pipeline schedules, the serving farm's
device slices — adds config surface that today only fails at trace
time, deep inside jax internals (`_SpecError` stacks; ROADMAP item 1).
meshlint extends the same pass pipeline (Diagnostic records, registry,
fix hints, crash-isolation) to sharded executions:

    mesh-spec               every PartitionSpec vs the declared mesh
                            (axis exists and is used once,
                            divisibility, rank): what jax.shard_map
                            rejects at trace time
    collective-consistency  per-member collective sequences under a
                            policy (gradsync bucket order, pipeline
                            schedule, sparse exchange); conditional
                            collectives that can deadlock
    donation-aliasing       fetches aliasing donated persistable state;
                            identity-cached feeds a later op mutates
    device-footprint        per-member byte estimate (params +
                            optimizer state + gradsync EF + KV cache)
                            vs the device memory cap, pre-compile
    mesh-recompile-hazard   static twin of the tpuscope recompile
                            explainer, phrased with the SAME ckey
                            component vocabulary (telemetry/ckey_vocab)
    kern-capability         program ops served by a registered Pallas
                            kernel (ops/kern) whose static probe
                            rejects the per-shard declared shapes —
                            the op lowers its jnp fallback, correct
                            but unaccelerated

Entry points: ParallelExecutor.verify() / FarmConfig.verify() (and
their PADDLE_TPU_VALIDATE pre-trace gates), tools/tpulint.py, and
`classify.green_configs` — the passing parallel tests' configs, on
which every pass must stay silent.

The validate-off path never imports this package (bench-contract pin);
keep every import of meshlint lazy.
"""
from .context import (MESH_PASSES, MeshLintContext, MeshSpec,
                      ShardMapUse, mesh_pass, mesh_pass_names,
                      normalize_spec, run_mesh_passes, spec_str,
                      verify_mesh)
from .spec_check import static_spec_verdict
from . import spec_check, collectives, donation, footprint, recompile, kerncap  # noqa: F401 (pass registration)
from .classify import green_configs

__all__ = [
    "MESH_PASSES", "MeshLintContext", "MeshSpec", "ShardMapUse",
    "mesh_pass", "mesh_pass_names", "normalize_spec", "run_mesh_passes",
    "spec_str", "verify_mesh",
    "static_spec_verdict",
    "green_configs",
]
