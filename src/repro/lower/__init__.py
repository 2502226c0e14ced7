"""The unified NTX lowering pipeline (layer spec -> NtxProgram -> backends).

    spec = Conv2dSpec(in_h=16, in_w=16, cin=8, kh=3, kw=3, cout=4)
    prog = lower(spec, "dx")                   # command-level §3.2 backward
    outs = run_reference(prog, {"dy": dy, "w": w})   # numpy ground truth
    res  = run_timing(prog, n_clusters=4)      # event-driven cycle estimate
    outs = run_pallas(prog, {"dy": dy, "w": w})      # Pallas kernels

One lowering rule per layer type serves the interpreter, the timing model,
and the TPU backend. Above the per-layer rules sits the network-graph
compiler (:mod:`repro.lower.graph`): a whole training step — forward, loss
gradient, backward, SGD update — compiles to ONE NtxProgram with
liveness-allocated TCDM, consumed unchanged by all three executors:

    graph = paper_cnn_graph(batch=8)
    prog  = lower_training_step(graph)         # one program per train step
    outs  = run_pallas(prog, {"x": x, "onehot": y1h, **params})

Above that again sits mesh data parallelism (:mod:`repro.lower.mesh`): the
compiled step shards across a mesh of HMCs with an explicit
gradient-allreduce epilogue, bit-identical under ``run_reference`` and
``shard_map``-parallel under ``run_pallas``:

    sharded = shard_training_step(graph, mesh_shape=(2, 2))
    outs    = run_pallas(sharded.program, inputs)   # psum allreduce

On the Pallas path, whole-step programs route through the region fuser
(:mod:`repro.lower.fuse`): contiguous compatible chains execute as single
double-buffered fused kernels and the step compiles to ONE cached
callable; ``run_pallas(..., fuse=False)`` is the per-node escape hatch.

See docs/architecture.md ("The lowering pipeline", "The graph compiler",
"Mesh execution", "The region fuser").
"""

from repro.lower.executors import (
    BatchedSpec,
    PLAN_CACHE,
    PlanCache,
    run_pallas,
    run_reference,
    run_timing,
)
from repro.lower.fuse import (
    FusionPlan,
    RegionSpec,
    plan_fusion,
)
from repro.lower.graph import (
    GraphNode,
    NetworkGraph,
    edge_consumers,
    frequency_band_batches,
    lm_token_batches,
    lower_training_step,
    one_hot_rows,
    paper_cnn_graph,
    softmax_xent_loss,
    train_graph,
)
from repro.lower.mesh import (
    ShardedTrainStep,
    parse_mesh,
    reshard_training_step,
    shard_training_step,
)
from repro.lower.ir import (
    ELEM_BYTES,
    CommandBlock,
    DesignPoint,
    LivenessAllocator,
    NS_DESIGN,
    NTX_DESIGN,
    NtxProgram,
    RegionAllocator,
    TensorRegion,
)
from repro.lower.rules import (
    AttentionSpec,
    BiasSpec,
    Conv2dSpec,
    EmbeddingSpec,
    FlattenSpec,
    LayerNormSpec,
    MatmulSpec,
    MaxPool2dSpec,
    PASSES,
    PosEmbedSpec,
    ReluSpec,
    ResidualAddSpec,
    SgdUpdateSpec,
    SoftmaxXentSpec,
    lower,
    lower_layer,
    register_lowering,
    supported_matrix,
)

__all__ = [
    "ELEM_BYTES",
    "AttentionSpec",
    "BatchedSpec",
    "BiasSpec",
    "CommandBlock",
    "Conv2dSpec",
    "DesignPoint",
    "EmbeddingSpec",
    "FlattenSpec",
    "FusionPlan",
    "GraphNode",
    "LayerNormSpec",
    "LivenessAllocator",
    "MatmulSpec",
    "MaxPool2dSpec",
    "NS_DESIGN",
    "NTX_DESIGN",
    "NetworkGraph",
    "NtxProgram",
    "PASSES",
    "PLAN_CACHE",
    "PlanCache",
    "PosEmbedSpec",
    "RegionAllocator",
    "RegionSpec",
    "ReluSpec",
    "ResidualAddSpec",
    "SgdUpdateSpec",
    "ShardedTrainStep",
    "SoftmaxXentSpec",
    "TensorRegion",
    "edge_consumers",
    "frequency_band_batches",
    "lm_token_batches",
    "parse_mesh",
    "plan_fusion",
    "reshard_training_step",
    "shard_training_step",
    "lower",
    "lower_layer",
    "lower_training_step",
    "one_hot_rows",
    "paper_cnn_graph",
    "register_lowering",
    "softmax_xent_loss",
    "supported_matrix",
    "train_graph",
]
