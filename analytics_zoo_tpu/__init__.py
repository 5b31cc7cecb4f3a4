"""analytics_zoo_tpu — a TPU-native analytics + AI framework with the
capabilities of robert-sbd/analytics-zoo, re-designed for JAX/XLA/pjit/pallas.

Layer map (mirrors SURVEY.md §1):
  common/    runtime bring-up (ZooContext ≅ NNContext), triggers
  feature/   data layer (FeatureSet + DiskFeatureSet, image/image3d/text
             pipelines, Preprocessing combinators)
  native/    ctypes binding for the C++ host IO library (native/zoo_io.cc)
  pipeline/  model API (keras/keras2 + autograd + onnx + Net/TorchNet/
             TFNet frozen-graph import), estimator, nnframes, inference
             runtime (bf16 + calibrated static int8)
  models/    built-in model zoo (recommendation, anomaly detection, text,
             seq2seq, image classification, object detection, caffe import)
  ops/       attention (window, grouped heads, rotary) + the experts'
             grouped product + pallas TPU kernels (flash attention, int8
             matmul)
  parallel/  mesh (data/pipe/seq/expert/model axes), shardings, ring
             attention, GPipe pipeline schedule; the two expert layers
             live with the layers (SparseMoE: capacity-bounded dispatch
             over the `expert` axis; RoutedExperts: dropless, one chip's
             share of the experts); multi-host bring-up in common/
  serving/   cluster-serving equivalent (stream, batching, backpressure)
  tfpark/    BERT estimators, GANEstimator, torch weight import
  ray/       task/actor runtime (RayOnSpark role)
  utils/     tensorboard writer/reader, checkpointing, profiling, proto
"""

__version__ = "0.1.0"

from .common.context import init_zoo_context, get_zoo_context  # noqa: F401
