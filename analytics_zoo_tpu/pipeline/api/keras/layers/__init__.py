from ..engine import Input, InputLayer, Lambda  # noqa: F401
from .core import (Activation, Dense, Dropout, Flatten, Reshape, Permute,  # noqa: F401
                   RepeatVector, Merge, merge, Select, Squeeze, ExpandDim,
                   Narrow, Masking, GaussianNoise, GaussianDropout,
                   TimeDistributed, Highway, SparseDense, get_activation,
                   GatedFeedForward)
from .embeddings import (Embedding, ShardedEmbedding, SparseEmbedding,  # noqa: F401
                         WordEmbedding)
from .normalization import (BatchNormalization, LayerNorm,  # noqa: F401
                            L2Normalize, RMSNorm)
from .convolution import (AtrousConvolution1D, AtrousConvolution2D,  # noqa: F401
                          Convolution1D, Convolution2D, Cropping1D,
                          Cropping2D, Deconvolution2D,
                          DepthwiseConvolution2D, LocallyConnected1D,
                          SeparableConvolution1D,
                          SeparableConvolution2D, ShareConvolution2D,
                          UpSampling1D, UpSampling2D,
                          ZeroPadding1D, ZeroPadding2D)
from .convolution3d import (ConvLSTM2D, ConvLSTM3D, Convolution3D,  # noqa: F401
                            Cropping3D, LRN2D, LocallyConnected2D,
                            MaxoutDense, SpatialDropout1D, SpatialDropout2D,
                            SpatialDropout3D, UpSampling3D,
                            WithinChannelLRN, ZeroPadding3D)
from .pooling import (AveragePooling1D, AveragePooling2D, AveragePooling3D,  # noqa: F401
                      GlobalAveragePooling1D, GlobalAveragePooling2D,
                      GlobalAveragePooling3D, GlobalMaxPooling1D,
                      GlobalMaxPooling2D, GlobalMaxPooling3D, KMaxPooling,
                      MaxPooling1D, MaxPooling2D, MaxPooling3D)
from .advanced_activations import (ELU, BinaryThreshold, HardShrink,  # noqa: F401
                                   HardTanh, LeakyReLU, PReLU, RReLU, SReLU,
                                   SoftShrink, Softmax, Threshold,
                                   ThresholdedReLU)
from .elementwise import (AddConstant, CAdd, CMul, Exp, Expand,  # noqa: F401
                          GaussianSampler, Log, Max, Mul, MulConstant,
                          Negative, Power, ResizeBilinear, Scale, Sqrt,
                          Square)
from .gpipe import GPipe, Pipeline  # noqa: F401
from .moe import RoutedExperts, SparseMoE  # noqa: F401
from .recurrent import GRU, LSTM, Bidirectional, SimpleRNN  # noqa: F401
from .self_attention import (BERT, DecoderAttention, DecoderBlock,  # noqa: F401
                             DecoderStack, LatentAttention,
                             MultiHeadSelfAttention, ShortConvMixer,
                             TransformerBlock, TransformerLayer)
