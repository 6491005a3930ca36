"""Host-side data pipelines (L1).

Loaders re-implement the reference's dataset semantics (SURVEY.md §2.5) —
FlyingChairs ppm/flo pairs with the official split file, Sintel T-frame
sliding-window volumes, UCF-101 class-balanced pair sampling — plus a
synthetic dataset for tests/benchmarks, behind one `Dataset` protocol, with
a multi-worker batch-assembly pipeline with deterministic ordering
(`pipeline.py`), and an async double-buffered prefetcher replacing the
reference's synchronous per-step cv2 reads (`sintelTrain.py:190`).
"""

from .augmentation import (
    apply_geo,
    augment_batch,
    identity_geo_params,
    make_augment_fn,
    photometric_augment,
    sample_geo_params,
)
from .datasets import (
    Dataset,
    FlyingChairsData,
    SintelData,
    SyntheticData,
    TokenData,
    UCF101Data,
    build_dataset,
)
from .mixture import MixtureDataset, build_mixture
from .pipeline import InputPipeline, derive_batch_rng
from .prefetch import Prefetcher

__all__ = [
    "apply_geo",
    "augment_batch",
    "identity_geo_params",
    "make_augment_fn",
    "photometric_augment",
    "sample_geo_params",
    "Dataset",
    "FlyingChairsData",
    "SintelData",
    "SyntheticData",
    "TokenData",
    "UCF101Data",
    "build_dataset",
    "MixtureDataset",
    "build_mixture",
    "InputPipeline",
    "derive_batch_rng",
    "Prefetcher",
]
