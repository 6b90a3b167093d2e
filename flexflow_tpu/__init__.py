"""flexflow_tpu: a TPU-native distributed DNN training framework.

A from-scratch re-design of the capabilities of FlexFlow (the Legion/CUDA
reference at github.com/vincent-163/FlexFlow) for TPU hardware: the lazy
FFModel builder graph lowers to a single jitted SPMD step over a
``jax.sharding.Mesh``; the Unity Partition/Combine/Replicate/Reduction
algebra lowers to GSPMD sharding transitions; collectives ride ICI/DCN via
XLA instead of NCCL. See SURVEY.md at the repo root for the full design
mapping.
"""

from .ffconst import (
    ActiMode,
    AggrMode,
    CompMode,
    DataType,
    LossType,
    MetricsType,
    OpType,
    ParameterSyncType,
    PoolType,
)
from .config import FFConfig, FFIterationConfig
from .core.machine import (
    DATA_AXIS,
    EXPERT_AXIS,
    MODEL_AXIS,
    PIPE_AXIS,
    SEQ_AXIS,
    MachineView,
    make_mesh,
)
from .core.parallel_tensor import ParallelDim, ParallelTensorShape
from .core.tensor import Parameter, Tensor
from .core.layer import Layer

# import op modules for registration side effects
from .ops import (  # noqa: F401
    attention,
    block_sparse_attention,
    conv,
    dropout,
    element_binary,
    element_unary,
    embedding,
    fused,
    gated_delta,
    lightning_attention,
    mamba2,
    linear,
    moe_ops,
    norm,
    parallel_ops,
    recurrent,
    reduce,
    softmax,
    stream_mix,
    structural,
)

from .runtime.model import FFModel
from .runtime.optimizer import AdamOptimizer, Optimizer, SGDOptimizer
from .runtime.initializer import (
    ConstantInitializer,
    GlorotUniformInitializer,
    NormInitializer,
    UniformInitializer,
    ZeroInitializer,
)
from .runtime.dataloader import DataLoaderGroup, Prefetcher, SingleDataLoader
from .runtime.guard import DivergenceError, TrainingGuard
from .runtime.metrics import PerfMetrics
from .analysis import (PCGValidationError, ValidationReport, lint_strategy,
                       validate_pcg)

__version__ = "0.1.0"
