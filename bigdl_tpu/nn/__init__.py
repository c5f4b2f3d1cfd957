from bigdl_tpu.nn.module import (
    Module, Container, Sequential, Concat, ConcatTable, ParallelTable,
    Identity, Lambda, CAddTable, CMulTable, JoinTable, SelectTable,
)
from bigdl_tpu.nn.layers import (
    Linear, Dense, Conv2D, SpatialConvolution, Conv1D, TemporalConvolution,
    MaxPool2D, AvgPool2D, GlobalAvgPool2D, SpatialMaxPooling,
    SpatialAveragePooling, BatchNorm, BatchNormalization,
    SpatialBatchNormalization, LayerNorm, RMSNorm, Dropout, Reshape, View,
    Flatten, Squeeze, Unsqueeze, Transpose, Embedding, LookupTable,
    ZeroPadding2D, ReLU, ReLU6, Tanh, Sigmoid, GELU, SiLU, Swish, SoftPlus,
    SoftSign, HardSigmoid, HardSwish, SoftMax, LogSoftMax, LeakyReLU,
    ELU, HardTanh, PReLU,
)
from bigdl_tpu.nn.layers_extra import (
    Conv3D, VolumetricConvolution, Conv2DTranspose, SpatialFullConvolution,
    Deconvolution2D, DepthwiseConv2D, SeparableConv2D,
    SpatialSeparableConvolution, LocallyConnected2D, MaxPool1D, AvgPool1D,
    TemporalMaxPooling, MaxPool3D, AvgPool3D, VolumetricMaxPooling,
    VolumetricAveragePooling, GlobalMaxPool2D, GlobalMaxPool1D,
    GlobalAvgPool1D, UpSampling2D, ResizeBilinear, UpSampling1D, UpSampling3D,
    Cropping2D, Cropping1D, Cropping3D, ZeroPadding1D, ZeroPadding3D, Padding, Power,
    Square, Sqrt, Log, Exp, Abs, Negative, Clamp, AddConstant, MulConstant,
    Threshold, SoftMin, LogSigmoid, ThresholdedReLU, Sum, Mean, Max, Min,
    CMul, CAdd, Mul, Add, Scale, CSubTable, CDivTable, CMaxTable, CMinTable,
    CAveTable, MM, MV, DotProduct, CosineDistance, PairwiseDistance,
    NarrowTable, FlattenTable, Select, Narrow, Masking, RepeatVector, Permute,
    Normalize, LRN, SpatialCrossMapLRN, SpatialDropout2D, SpatialDropout1D,
    GaussianNoise, GaussianDropout, Highway, Maxout, Bilinear, Cosine,
    Euclidean, SReLU,
)
from bigdl_tpu.nn.layers_more import (
    SplitTable, Pack, Replicate, Reverse, MixtureTable, MapTable, Bottle,
    InferReshape, GradientReversal, L1Penalty, HardShrink, SoftShrink,
    TanhShrink, Mish, RReLU, GaussianSampler, Conv3DTranspose,
    VolumetricFullConvolution, LocallyConnected1D, GlobalMaxPool3D,
    GlobalAvgPool3D, ConvLSTM2D, ConvLSTMPeephole,
    SpatialSubtractiveNormalization, SpatialDivisiveNormalization,
    SpatialContrastiveNormalization,
)
from bigdl_tpu.nn import ops_layers as ops_layers  # noqa: F401
from bigdl_tpu.nn.ops_layers import *  # noqa: F401,F403 — TF-op tranche (nn/ops)
from bigdl_tpu.nn.sparse_layers import SparseLinear, SparseJoinTable
from bigdl_tpu.nn.layers_misc import (
    LookupTableSparse, SpatialWithinChannelLRN, NormalizeScale, Echo,
    RoiPooling, SpatialShareConvolution, SpatialDilatedConvolution,
    CTCCriterion, ClassSimplexCriterion, WeightedMSECriterion,
    Index, BifurcateSplitTable, NegativeEntropyPenalty,
    Contiguous, Copy, Unfold, SpatialDropout3D, VolumetricDropout,
    MultiLabelMarginCriterion, SmoothL1CriterionWithWeights,
)
from bigdl_tpu.nn.rnn import (
    SimpleRNN, LSTM, LSTMPeephole, GRU, BiRecurrent, TimeDistributed,
    RecurrentDecoder, RnnCell, Recurrent, MultiRNNCell,
)
from bigdl_tpu.nn.decode import beam_search, greedy_decode, DecodeResult
from bigdl_tpu.nn.attention import (
    MultiHeadAttention, PositionwiseFFN, PositionalEncoding,
    TransformerLayer, TransformerDecoderLayer, Transformer, Attention,
    FeedForwardNetwork, dot_product_attention, positional_encoding,
    transformer_decode, transformer_decode_cached, LatentAttention, rope,
)
from bigdl_tpu.nn.criterion import (
    Criterion, ClassNLLCriterion, CrossEntropyCriterion, MSECriterion,
    AbsCriterion, SmoothL1Criterion, BCECriterion, BCEWithLogitsCriterion,
    KLDivCriterion, CosineEmbeddingCriterion, MarginRankingCriterion,
    ParallelCriterion, TimeDistributedCriterion,
)
from bigdl_tpu.nn.layers_tail import (
    ActivityRegularization, Anchor, BinaryThreshold, BinaryTreeLSTM,
    CrossProduct, DenseToSparse, DetectionOutputFrcnn, DetectionOutputSSD,
    ExpandSize, GroupNorm, InstanceNorm1D, InstanceNorm2D, InstanceNorm3D,
    MaskedSelect, PriorBox, Proposal, SequenceBeamSearch,
    SpatialConvolutionMap, SpatialZeroPadding,
)
from bigdl_tpu.nn.criterion_extra import (
    MultiCriterion, MultiLabelSoftMarginCriterion, MultiMarginCriterion,
    HingeEmbeddingCriterion, L1HingeEmbeddingCriterion, MarginCriterion,
    SoftMarginCriterion, DiceCoefficientCriterion, PoissonCriterion,
    DistKLDivCriterion, KullbackLeiblerDivergenceCriterion,
    MeanAbsolutePercentageCriterion, MeanSquaredLogarithmicCriterion,
    CategoricalCrossEntropy, CosineDistanceCriterion,
    CosineProximityCriterion, RankHingeCriterion, GaussianCriterion,
    KLDCriterion, L1Cost, TransformerCriterion,
    TimeDistributedMaskCriterion, PGCriterion,
)


def __getattr__(name):
    # reference ``nn/Graph.scala`` — the node-graph container lives in the
    # keras engine (one implementation); lazy import avoids a cycle
    if name == "Graph":
        from bigdl_tpu.keras.engine import Model as Graph

        return Graph
    if name == "Input":
        from bigdl_tpu.keras.engine import Input

        return Input
    raise AttributeError(f"module 'bigdl_tpu.nn' has no attribute {name!r}")
