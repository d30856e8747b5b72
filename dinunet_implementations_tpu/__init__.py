"""dinunet-tpu: TPU-native federated deep-learning framework.

A ground-up re-design of the capabilities of trendscenter/dinunet_implementations
(COINSTAC dinunet — decentralized NN training across sites) for TPU:

- each federated site maps to a slice of a ``jax.sharding.Mesh`` ("site" axis);
- the reference's local↔remote JSON round trip collapses into one pjit SPMD
  train step; aggregation engines (dSGD / rankDAD / powerSGD) are XLA
  collectives + in-jit low-rank compression;
- trainers/datasets/data-handles keep the reference's abstraction surface
  (SURVEY.md §2.3) with a functional JAX core.
"""

from .core.config import (
    AggEngine,
    FSArgs,
    ICAArgs,
    MultimodalArgs,
    NNComputation,
    PretrainArgs,
    SMRI3DArgs,
    TrainConfig,
    export_compspec,
    load_inputspec,
    resolve_site_configs,
)
from .parallel.mesh import (
    MODEL_AXIS,
    SITE_AXIS,
    SLICE_AXIS,
    host_mesh,
    make_site_mesh,
    sliced_site_mesh,
)

__version__ = "0.19.0"


def __getattr__(name):
    # Heavier subsystems are imported lazily so `import dinunet_implementations_tpu`
    # stays light for config-only uses.
    if name in ("run_checks", "sanitized_fit", "SanitizerViolation", "CompileGuard"):
        from . import checks

        return getattr(checks, name)
    if name in ("FedRunner", "SiteRunner"):
        from .runner import fed_runner

        return getattr(fed_runner, name)
    if name == "FederatedTrainer":
        from .trainer.loop import FederatedTrainer

        return FederatedTrainer
    if name in ("FaultPlan", "Preempted", "PreemptionGuard", "with_retry"):
        from . import robustness

        return getattr(robustness, name)
    if name in ("RdpAccountant", "SECURE_AGGS"):
        from . import privacy

        return getattr(privacy, name)
    if name in ("SpanTracer", "FitTelemetry"):
        from . import telemetry

        return getattr(telemetry, name)
    if name == "InferenceEngine":
        from .serving import InferenceEngine

        return InferenceEngine
    raise AttributeError(name)
