"""Framework-wide enums.

TPU-native re-design of the reference's constant surface
(reference: include/flexflow/ffconst.h — OpType/ActiMode/AggrMode/PoolType/
DataType/LossType/MetricsType/ParameterSyncType enums). Values are our own;
only the *names* mirror the reference so users of the reference find the
same vocabulary.
"""

from __future__ import annotations

import enum

import jax.numpy as jnp


class DataType(enum.Enum):
    """Tensor element types (reference: ffconst.h DT_*)."""

    BOOL = "bool"
    INT32 = "int32"
    INT64 = "int64"
    HALF = "float16"
    BFLOAT16 = "bfloat16"
    FLOAT = "float32"
    DOUBLE = "float64"
    NONE = "none"

    def to_jnp(self):
        if self is DataType.NONE:
            raise ValueError("DT_NONE has no jnp dtype")
        return jnp.dtype(self.value)

    @staticmethod
    def from_jnp(dtype) -> "DataType":
        return DataType(jnp.dtype(dtype).name)

    def itemsize(self) -> int:
        return int(self.to_jnp().itemsize)


class ActiMode(enum.Enum):
    """Fused activation modes (reference: ffconst.h AC_MODE_*)."""

    NONE = 10
    RELU = 11
    SIGMOID = 12
    TANH = 13
    GELU = 14
    SILU = 15
    RELU2 = 16      # relu(x)^2 (no reference analog)


class AggrMode(enum.Enum):
    """Embedding aggregation (reference: ffconst.h AGGR_MODE_*)."""

    NONE = 20
    SUM = 21
    AVG = 22


class PoolType(enum.Enum):
    """Pooling modes (reference: ffconst.h POOL_MAX/POOL_AVG)."""

    MAX = 30
    AVG = 31


class LossType(enum.Enum):
    """Loss functions (reference: ffconst.h LOSS_*)."""

    CATEGORICAL_CROSSENTROPY = 50
    SPARSE_CATEGORICAL_CROSSENTROPY = 51
    MEAN_SQUARED_ERROR_AVG_REDUCE = 52
    MEAN_SQUARED_ERROR_SUM_REDUCE = 53
    IDENTITY = 54


class MetricsType(enum.Enum):
    """Metrics (reference: ffconst.h METRICS_*)."""

    ACCURACY = 1001
    CATEGORICAL_CROSSENTROPY = 1002
    SPARSE_CATEGORICAL_CROSSENTROPY = 1003
    MEAN_SQUARED_ERROR = 1004
    ROOT_MEAN_SQUARED_ERROR = 1005
    MEAN_ABSOLUTE_ERROR = 1006


class ParameterSyncType(enum.Enum):
    """Gradient synchronization type per weight (reference: ffconst.h
    ParameterSyncType {NONE, PS, NCCL}).  On TPU both lower to XLA
    all-reduce/reduce-scatter emitted by the SPMD partitioner; the enum is
    kept for API parity and to mark weights that need no sync."""

    NONE = 80
    PS = 81
    ALL_REDUCE = 82  # reference calls this NCCL

    # alias for reference-API compatibility
    NCCL = 82


class CompMode(enum.Enum):
    """Computation mode (reference: ffconst.h COMP_MODE_TRAINING/INFERENCE)."""

    TRAINING = 70
    INFERENCE = 71


class OpType(enum.Enum):
    """Operator types (reference: ffconst.h OperatorType OP_*).

    One entry per compute operator in the reference inventory
    (SURVEY.md section 2.2) plus the parallel ops (section 2.3).
    """

    INPUT = "input"
    WEIGHT = "weight"
    NOOP = "noop"
    # baked-in constant tensor (no reference analog: HF imports fold
    # position-id buffers / masks into graph constants; XLA embeds them)
    CONSTANT = "constant"
    CONV2D = "conv2d"
    DROPOUT = "dropout"
    LINEAR = "linear"
    BATCHMATMUL = "batch_matmul"
    POOL2D = "pool2d"
    SCALAR_MULTIPLY = "scalar_multiply"
    SCALAR_ADD = "scalar_add"
    SCALAR_SUB = "scalar_sub"
    SCALAR_TRUE_DIV = "scalar_truediv"
    SCALAR_FLOOR_DIV = "scalar_floordiv"
    RELU = "relu"
    IDENTITY = "identity"
    SIGMOID = "sigmoid"
    TANH = "tanh"
    ELU = "elu"
    GELU = "gelu"
    RSQRT = "rsqrt"
    POW = "pow"
    SIN = "sin"
    COS = "cos"
    EXP = "exp"
    FLAT = "flat"
    SOFTMAX = "softmax"
    BATCHNORM = "batch_norm"
    LAYERNORM = "layer_norm"
    RMS_NORM = "rms_norm"
    # x -> scale * x + shift, two learned vectors over the last axis (a
    # model's learned residual scaling)
    SCALE_SHIFT = "scale_shift"
    CONCAT = "concat"
    SPLIT = "split"
    EMBEDDING = "embedding"
    GATHER = "gather"
    GROUP_BY = "group_by"
    CACHE = "cache"
    AGGREGATE = "aggregate"
    AGGREGATE_SPEC = "aggregate_spec"
    # stacked (single-tensor) MoE pipeline: the expert-parallel formulation
    GROUP_BY_STACKED = "group_by_stacked"
    EXPERT_LINEAR = "expert_linear"
    AGGREGATE_STACKED = "aggregate_stacked"
    RESHAPE = "reshape"
    SLICE = "slice"
    REVERSE = "reverse"
    TRANSPOSE = "transpose"
    EW_ADD = "add"
    EW_MUL = "multiply"
    EW_SUB = "subtract"
    EW_DIV = "divide"
    EW_MAX = "max"
    EW_MIN = "min"
    REDUCE_SUM = "reduce_sum"
    MEAN = "mean"
    CAST = "cast"
    TOPK = "topk"
    MULTIHEAD_ATTENTION = "multihead_attention"
    # causal self-attention whose keys and values are up-projections of
    # one low-rank latent row a token, with rotary positions (MLA)
    LATENT_ATTENTION = "latent_attention"
    # linear attention by the gated delta rule: a state of fixed size a
    # sequence, a short causal convolution before it (Gated DeltaNet)
    GATED_DELTA_NET = "gated_delta_net"
    # the gated delta rule with a decay a key CHANNEL behind a sigmoid
    # bounded below, and one output gate a head (Kimi Delta Attention)
    KIMI_DELTA_ATTENTION = "kimi_delta_attention"
    # causal attention with grouped key-value heads that, past a context
    # length, reads only the key blocks a score over mean-pooled keys
    # selects (InfLLM v2), and an output gate
    BLOCK_SPARSE_ATTENTION = "block_sparse_attention"
    # linear attention with a fixed decay a head over a state of fixed
    # size a sequence, rotary positions (Lightning Attention)
    LIGHTNING_ATTENTION = "lightning_attention"
    # causal grouped-head attention whose queries and keys are mixed along
    # the sequence by two short causal convolutions, whose values take
    # half of each head from the token before, and whose positions rotate
    # part of a head (compressed convolutional attention)
    COMPRESSED_CONV_ATTENTION = "compressed_conv_attention"
    # a state-space mixer (Mamba-2): a scalar data-dependent decay a head
    # over a state of fixed size a sequence, B and C shared by the heads
    # of a group, a joint causal convolution, a gated grouped RMSNorm
    MAMBA2 = "mamba2"
    # x -> (act(x W_gate) * (x W_up)) W_down
    GATED_MLP = "gated_mlp"
    # a residual path of n streams (hyper-connections): the streams spread
    # from one, read into a sublayer's input, written back behind it under
    # a mixing matrix, and summed at the end
    STREAM_MIX = "stream_mix"
    # dropless top-k routing over n experts, of which this op holds a
    # contiguous share and computes only those
    ROUTED_EXPERTS = "routed_experts"
    # recurrent ops (reference: the legacy NMT engine's LSTM/RNN cells,
    # /root/reference/nmt/{rnn.h,lstm.cu} — predating FFModel; first-class
    # ops here)
    LSTM = "lstm"
    RNN = "rnn"
    GRU = "gru"
    FUSED = "fused"
    # parallel ops (reference: src/parallel_ops)
    REPARTITION = "repartition"
    COMBINE = "combine"
    REPLICATE = "replicate"
    REDUCTION = "reduction"
    ALLREDUCE = "allreduce"
    FUSED_PARALLEL = "fused_parallel"
