from keystone_tpu.workflow.graph import Graph, GraphId, NodeId, SourceId
from keystone_tpu.workflow.pipeline import (
    Estimator,
    FusedTransformer,
    LabelEstimator,
    Pipeline,
    PipelineDataset,
    Transformer,
)
from keystone_tpu.workflow.analysis import (
    Diagnostic,
    LintError,
    LintReport,
    lint_graph,
)
from keystone_tpu.workflow.executor import GraphExecutor, PipelineEnv
from keystone_tpu.workflow.operators import placed_batch
from keystone_tpu.workflow.functional import fitted_forward
from keystone_tpu.workflow.optimizer import (
    ChainFusionRule,
    EquivalentNodeMergeRule,
    Optimizer,
    Rule,
    default_optimizer,
)
from keystone_tpu.workflow.serialization import (
    ArtifactVersionError,
    ModelArtifact,
    load_artifact,
    load_pipeline,
    save_artifact,
    save_pipeline,
)
from keystone_tpu.workflow.online import (
    OnlineState,
    OnlineStateError,
    OnlineTrainer,
    supports_partial_fit,
)
from keystone_tpu.workflow.serving import (
    CompiledPipeline,
    DeadlineExceeded,
    PipelineService,
    QueueFullError,
    RowDependenceError,
    ServiceClosed,
    WorkerDiedError,
)

__all__ = [
    "Graph",
    "GraphId",
    "NodeId",
    "SourceId",
    "Transformer",
    "FusedTransformer",
    "Estimator",
    "LabelEstimator",
    "Pipeline",
    "PipelineDataset",
    "PipelineEnv",
    "GraphExecutor",
    "fitted_forward",
    "placed_batch",
    "Optimizer",
    "Rule",
    "ChainFusionRule",
    "EquivalentNodeMergeRule",
    "default_optimizer",
    "save_pipeline",
    "load_pipeline",
    "save_artifact",
    "load_artifact",
    "ModelArtifact",
    "ArtifactVersionError",
    "OnlineState",
    "OnlineStateError",
    "OnlineTrainer",
    "supports_partial_fit",
    "Diagnostic",
    "LintError",
    "LintReport",
    "lint_graph",
    "CompiledPipeline",
    "PipelineService",
    "RowDependenceError",
    "QueueFullError",
    "DeadlineExceeded",
    "ServiceClosed",
    "WorkerDiedError",
]
