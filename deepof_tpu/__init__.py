"""deepof_tpu — TPU-native framework for Guided Optical Flow Learning.

A from-scratch JAX/XLA/Pallas/pjit re-design with the capabilities of the
reference TF1 implementation (bryanyzhu/deepOF): unsupervised optical-flow
training via multi-scale photometric warp losses over FlowNet-S / VGG16 /
Inception-v3 encoder-decoders, multi-frame Sintel volumes, UCF-101 two-stream
action models, plus TPU-first additions (data-parallel pjit over device
meshes, spatial context parallelism with halo exchange, Pallas fused kernels,
FlowNet-C correlation).

Layout:
  core/     config dataclasses, train-state pytrees, PRNG plumbing
  io/       .flo Middlebury IO, split files, image decode
  data/     dataset pipelines + on-device augmentation + prefetch
  models/   flax.linen model zoo
  ops/      warp / smoothness / LRN / correlation ops (+ ops/pallas kernels)
  losses/   multi-scale unsupervised pyramid losses
  parallel/ mesh construction, sharding rules, halo exchange
  train/    pjit train step, schedules, checkpointing, eval, logging
  utils/    metrics (EPE/AAE), flow color viz
"""

import time as _time

#: `time.perf_counter()` on the package's first line: where set-up's
#: `boot` span ends and its `import` span starts (`obs.trace.record_setup`)
T_PACKAGE_START = _time.perf_counter()

__version__ = "0.1.0"
