"""bigdl_tpu.nn — the layer library.

TPU-native re-design of the reference's ``com.intel.analytics.bigdl.nn``
(SURVEY.md §2.3): Torch-style stateful modules whose forward code is jax and
traces into pure XLA programs via ``bigdl_tpu.nn.module.pure_apply``.
"""

from bigdl_tpu.nn.module import Module, pure_apply, bind, abstract_init
from bigdl_tpu.nn import init
from bigdl_tpu.nn.container import (
    Container, Sequential, Concat, ConcatTable, ParallelTable, MapTable, Bottle,
)
from bigdl_tpu.nn.linear import (
    Linear, Bilinear, Add, Mul, CMul, CAdd, Scale, Euclidean, Cosine,
)
from bigdl_tpu.nn.conv import (
    SpatialConvolution, SpatialDilatedConvolution, SpatialFullConvolution,
    SpatialSeparableConvolution, SpatialShareConvolution, LocallyConnected2D,
    TemporalConvolution, VolumetricConvolution,
)
from bigdl_tpu.nn.pooling import (
    SpatialMaxPooling, SpatialAveragePooling, TemporalMaxPooling,
    VolumetricMaxPooling, VolumetricAveragePooling,
)
from bigdl_tpu.nn.activation import (
    ReLU, ReLU6, Threshold, BinaryThreshold, Tanh, TanhShrink, Sigmoid,
    HardSigmoid, HardTanh, Clamp, ELU, LeakyReLU, PReLU, RReLU, SReLU,
    SoftPlus, SoftSign, SoftShrink, HardShrink, SoftMax, SoftMin, LogSoftMax,
    LogSigmoid, Exp, Log, Log1p, Sqrt, Square, Power, Abs, Negative,
    AddConstant, MulConstant, GradientReversal, Identity, Echo, Maxout,
    L1Penalty, NegativeEntropyPenalty,
)
from bigdl_tpu.nn.shape_ops import (
    Reshape, View, Squeeze, Unsqueeze, Transpose, Select, Narrow, Replicate,
    Tile, Padding, SpatialZeroPadding, Contiguous, Index, MaskedSelect,
    Masking, Reverse, InferReshape, Cropping2D, Cropping3D, UpSampling1D,
    UpSampling2D, UpSampling3D, ResizeBilinear, Pack,
)
from bigdl_tpu.nn.table_ops import (
    CAddTable, CMulTable, CSubTable, CDivTable, CMaxTable, CMinTable,
    CAveTable, JoinTable, SplitTable, BifurcateSplitTable, NarrowTable,
    SelectTable, FlattenTable, MixtureTable, MM, MV, DotProduct,
    CosineDistance, PairwiseDistance, CrossProduct, Sum, Mean, Max, Min,
)
from bigdl_tpu.nn.dropout import (
    Dropout, SpatialDropout1D, SpatialDropout2D, SpatialDropout3D,
    GaussianDropout, GaussianNoise, GaussianSampler,
)
from bigdl_tpu.nn.normalization import (
    BatchNormalization, SpatialBatchNormalization, VolumetricBatchNormalization,
    Normalize, NormalizeScale, SpatialCrossMapLRN, SpatialWithinChannelLRN,
    SpatialSubtractiveNormalization, SpatialDivisiveNormalization,
    SpatialContrastiveNormalization,
)
from bigdl_tpu.nn.embedding import LookupTable
from bigdl_tpu.nn.graph import Graph, StaticGraph, DynamicGraph, Node, Input
from bigdl_tpu.nn.recurrent import (
    Cell, RnnCell, LSTM, LSTMPeephole, GRU, ConvLSTMPeephole,
    ConvLSTMPeephole3D, MultiRNNCell,
    Recurrent, BiRecurrent, RecurrentDecoder, TimeDistributed,
)
from bigdl_tpu.nn.attention import (
    LayerNorm, MultiHeadAttention, RMSNorm, TransformerBlock,
    dot_product_attention,
)
from bigdl_tpu.nn.gated_delta import GatedDeltaNet, GatedMLP
from bigdl_tpu.nn.lightning_attention import LightningAttention
from bigdl_tpu.nn.sparse_attention import BlockSparseAttention
from bigdl_tpu.nn.latent_attention import LatentAttention
from bigdl_tpu.nn.routed_experts import RoutedExperts
from bigdl_tpu.nn.criterion import (
    Criterion, ClassNLLCriterion, CrossEntropyCriterion, CategoricalCrossEntropy,
    MSECriterion, AbsCriterion, BCECriterion, SmoothL1Criterion,
    DistKLDivCriterion, KLDCriterion, GaussianCriterion, MarginCriterion,
    HingeEmbeddingCriterion, L1HingeEmbeddingCriterion, CosineEmbeddingCriterion,
    MarginRankingCriterion, MultiMarginCriterion, MultiLabelMarginCriterion,
    MultiLabelSoftMarginCriterion, SoftMarginCriterion, L1Cost,
    DotProductCriterion, CosineDistanceCriterion, CosineProximityCriterion,
    PoissonCriterion, MeanAbsolutePercentageCriterion,
    MeanSquaredLogarithmicCriterion, KullbackLeiblerDivergenceCriterion,
    DiceCoefficientCriterion, ClassSimplexCriterion, ParallelCriterion,
    MultiCriterion, TimeDistributedCriterion, PGCriterion,
    ActivityRegularization, SmoothL1CriterionWithWeights,
    SoftmaxWithCriterion, TimeDistributedMaskCriterion, TransformerCriterion,
)
from bigdl_tpu.nn import ops  # TF-style Operation modules (nn/ops/, SURVEY.md §2.3)
from bigdl_tpu.nn import tf_ops  # TF infra ops (nn/tf/, SURVEY.md §2.3)
from bigdl_tpu.nn.tf_ops import (
    WhileLoop, If, ControlNodes, Variable, Assign, AssignAdd, AssignSub,
    TensorArray, ParseExample,
)
from bigdl_tpu.nn.sparse import (
    DenseToSparse, LookupTableSparse, SparseJoinTable, SparseLinear,
    SparseMiniBatch, SparseTensor,
)
from bigdl_tpu.nn.detection import (
    Anchor, DetectionOutputFrcnn, DetectionOutputSSD, Nms, PriorBox, Proposal,
    RoiPooling, bbox_iou, decode_boxes, nms,
)
from bigdl_tpu.nn.tree_lstm import BinaryTreeLSTM, TreeLSTM
from bigdl_tpu.nn.pooling import SpatialMaxPoolingWithIndices, SpatialUnpooling
from bigdl_tpu.nn.conv import (
    LocallyConnected1D, SpatialConvolutionMap, VolumetricFullConvolution,
)

# Reference-name aliases: nn/RNN (simple recurrent cell, ≙ nn/RNN.scala) and
# DynamicContainer (the add()-based container base, ≙ nn/DynamicContainer.scala
# — our Container already carries add()).
RNN = RnnCell
DynamicContainer = Container
