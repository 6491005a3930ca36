"""Dataset index builders and batch samplers.

Numpy/cv2 host-side loaders with decoded-image caching. Batches are dicts of
float32 numpy arrays, BGR channel order with per-dataset means preserved from
the reference (`flyingChairsLoader.py:28`, `sintelLoader.py:29`,
`version1/loader/ucf101Loader.py` mean [104,117,123]).

Split semantics:
  - FlyingChairs: official `FlyingChairs_train_val.txt` (one marker per
    sample, 1=train 2=val, `flyingChairsLoader.py:47-55`). Zero-egress: no
    auto-download; if the file is absent the last 640 samples become val
    (documented divergence from the reference's wget at
    `flyingChairsLoader.py:31-34`).
  - Sintel: all T-frame sliding windows per clip
    (`sintelLoader.py:31-45`); val = the first window of each clip in
    sorted-clip order, plus one extra bamboo_2 window starting at frame
    `time_step` — the reference's exact membership and order
    (`sintelLoader.py:47-70`: 23 clips + 1 = 24 windows), so EPE numbers
    are protocol-comparable at the 24-window granularity.
  - UCF-101: clip group number > 7 -> train (`ucf101Loader.py:42-58`);
    train batch = one random frame-pair from each of B distinct random
    classes (`ucf101Loader.py:66-87`).
"""

from __future__ import annotations

import collections
import os
import re
import threading
import warnings
from typing import Protocol

import numpy as np

try:  # pragma: no cover - exercised implicitly
    import cv2
except Exception:  # noqa: BLE001
    cv2 = None

from ..core.config import DataConfig
from ..io.flo import read_flo

FLYINGCHAIRS_MEAN = (97.533, 99.238, 97.056)  # BGR, flyingChairsLoader.py:28
SINTEL_MEAN = (70.1433, 83.1915, 92.8827)  # sintelLoader.py:29
UCF101_MEAN = (104.0, 117.0, 123.0)  # version1/loader/ucf101Loader.py

DATASET_MEANS = {
    "flyingchairs": FLYINGCHAIRS_MEAN,
    "sintel": SINTEL_MEAN,
    "ucf101": UCF101_MEAN,
    "synthetic": (0.0, 0.0, 0.0),
}


_warned_native_fallback = False


def _warn_native_fallback(err: Exception) -> None:
    """One warning per process: native batch IO failed (mixed formats,
    corrupt file, ...) and the affected batches take the python path."""
    global _warned_native_fallback
    if not _warned_native_fallback:
        _warned_native_fallback = True
        warnings.warn(
            f"native IO batch failed ({err}); affected batches fall back "
            "to the python decode path", RuntimeWarning, stacklevel=3)


def _imread_bgr(path: str) -> np.ndarray:
    img = cv2.imread(path, cv2.IMREAD_COLOR)  # BGR, matches reference cv2 use
    if img is None:
        raise FileNotFoundError(path)
    return img


def _resize(img: np.ndarray, hw: tuple[int, int]) -> np.ndarray:
    if img.shape[:2] == tuple(hw):
        return img
    return cv2.resize(img, (hw[1], hw[0]), interpolation=cv2.INTER_LINEAR)


class Dataset(Protocol):
    """Batch-sampler protocol shared by all datasets.

    `sample_train` returns a dict with at least the network-input tensors;
    `num_train`/`num_val` drive the epoch loop like the reference's
    `trainNum`/`valNum` (`version1/loader/flyingChairsLoader.py:26-36`).
    """

    mean: tuple[float, float, float]
    num_train: int
    num_val: int

    def sample_train(self, batch_size: int, iteration: int | None = None,
                     rng: np.random.RandomState | None = None) -> dict: ...

    def sample_val(self, batch_size: int, batch_id: int) -> dict: ...

    def cache_stats(self) -> dict: ...


class _DecodedCache:
    """Byte-bounded decoded-image cache (SURVEY.md §7.3.4: per-step host
    decode starves a TPU). LRU eviction keeps host RAM bounded even on the
    full 22k-pair FlyingChairs set.

    Thread-safe: the multi-worker input pipeline (`data/pipeline.py`)
    shares one cache across decode workers. The OrderedDict is guarded by
    a lock; misses decode OUTSIDE it so workers never serialize on cv2 —
    two threads missing the same path decode it twice (benign: identical
    result, last insert wins, double-counted bytes corrected on insert).
    Hit/miss/eviction counters surface in train logs and `bench.py`.
    """

    def __init__(self, enabled: bool, reader, max_bytes: int = 4 << 30):
        self._enabled = enabled
        self._reader = reader
        self._max_bytes = max_bytes
        self._bytes = 0
        self._store: collections.OrderedDict[str, np.ndarray] = (
            collections.OrderedDict())
        self._lock = threading.Lock()
        self._hits = 0
        self._misses = 0
        self._evictions = 0

    def __call__(self, path: str) -> np.ndarray:
        if not self._enabled:
            return self._reader(path)
        with self._lock:
            hit = self._store.pop(path, None)
            if hit is not None:
                self._hits += 1
                self._store[path] = hit  # re-insert as most recent
                return hit
            self._misses += 1
        decoded = self._reader(path)  # off-lock: decode is the slow part
        with self._lock:
            prev = self._store.pop(path, None)  # racing double-decode
            if prev is None:
                self._bytes += decoded.nbytes
            while self._bytes > self._max_bytes and self._store:
                _, old = self._store.popitem(last=False)
                self._bytes -= old.nbytes
                self._evictions += 1
            self._store[path] = decoded
        return decoded

    def stats(self) -> dict:
        with self._lock:
            return {"hits": self._hits, "misses": self._misses,
                    "evictions": self._evictions, "bytes": self._bytes,
                    "entries": len(self._store)}


class FlyingChairsData:
    """FlyingChairs pairs: `XXXXX_img1.ppm`, `XXXXX_img2.ppm`, `XXXXX_flow.flo`.

    Images are resized to `cfg.image_size`; ground-truth flow stays at its
    native resolution (`flyingChairsLoader.py:71-81`). Supports both the
    gen-2 sequential batching (`iteration` arg, `flyingChairsLoader.py:57-62`)
    and gen-1 random sampling (`version1/loader/flyingChairsLoader.py:66-70`).
    """

    mean = FLYINGCHAIRS_MEAN

    def __init__(self, cfg: DataConfig):
        self.cfg = cfg
        root = cfg.data_path
        ids = sorted(
            m.group(1)
            for f in os.listdir(root)
            if (m := re.match(r"(\d+)_img1\.ppm$", f))
        )
        if not ids:
            raise FileNotFoundError(f"no *_img1.ppm under {root}")
        split_file = os.path.join(root, "FlyingChairs_train_val.txt")
        if not os.path.exists(split_file):
            split_file = os.path.join(os.path.dirname(root), "FlyingChairs_train_val.txt")
        if os.path.exists(split_file):
            markers = np.loadtxt(split_file, dtype=int)[: len(ids)]
        else:  # zero-egress fallback: last 640 (capped at 10%, min 1) are val
            n_val = min(640, max(1, len(ids) // 10))
            markers = np.ones(len(ids), dtype=int)
            markers[-n_val:] = 2
        self.train_ids = [i for i, m in zip(ids, markers) if m == 1]
        self.val_ids = [i for i, m in zip(ids, markers) if m == 2]
        self.num_train, self.num_val = len(self.train_ids), len(self.val_ids)
        self._root = root
        self._cache = _DecodedCache(cfg.cache_decoded, _imread_bgr,
                                    max_bytes=cfg.cache_bytes)
        self._flo_hw: tuple[int, int] | None = None  # native path probe

    def _load(self, sid: str, with_flow: bool) -> tuple[np.ndarray, np.ndarray, np.ndarray | None]:
        p = os.path.join(self._root, sid)
        src = _resize(self._cache(p + "_img1.ppm"), self.cfg.image_size)
        tgt = _resize(self._cache(p + "_img2.ppm"), self.cfg.image_size)
        flow = read_flo(p + "_flow.flo") if with_flow else None
        return src, tgt, flow

    def _batch(self, sids: list[str]) -> dict:
        native = self._native_batch(sids)
        if native is not None:
            return native
        srcs, tgts, flows = zip(*(self._load(s, True) for s in sids))
        return {
            "source": np.stack(srcs).astype(np.float32),
            "target": np.stack(tgts).astype(np.float32),
            "flow": np.stack(flows).astype(np.float32),
        }

    def _native_batch(self, sids: list[str]) -> dict | None:
        """Whole-batch parallel decode through the C++ IO library (thread
        pool outside the GIL; deepof_tpu/native).

        Only used in streaming mode (`cache_decoded=False` — the right
        setting when the dataset exceeds the decoded-image cache, e.g. the
        full 22k-pair FlyingChairs set): with the cache enabled, warm RAM
        hits beat a fresh parallel decode, so the cv2+cache path wins.
        Falls back to that path when the library is unavailable.
        """
        from .. import native

        if self.cfg.cache_decoded or not native.available():
            return None
        paths = [os.path.join(self._root, s) for s in sids]
        try:
            if self._flo_hw is None:
                self._flo_hw = native.flo_dims(paths[0] + "_flow.flo")
            imgs = native.decode_ppm_batch(
                [p + sfx for sfx in ("_img1.ppm", "_img2.ppm") for p in paths],
                self.cfg.image_size)
            flows = native.read_flo_batch([p + "_flow.flo" for p in paths],
                                          self._flo_hw)
        except (OSError, RuntimeError) as e:
            # a later-unsupported/corrupt file must degrade to the python
            # path for this batch, not fail it (ADVICE r02)
            _warn_native_fallback(e)
            return None
        n = len(paths)
        return {"source": imgs[:n], "target": imgs[n:], "flow": flows}

    def sample_train(self, batch_size, iteration=None, rng=None):
        if iteration is not None:  # sequential, gen-2
            # wrap like sample_val: a num_train below batch_size (or a
            # start near the tail) must still yield exactly batch_size
            # samples — a short batch breaks the compiled executable's
            # fixed shapes
            if not self.num_train:
                raise ValueError(
                    f"empty FlyingChairs train split under {self._root} "
                    "(split file marks every pair as val)")
            start = (iteration * batch_size) % self.num_train
            sids = [self.train_ids[(start + k) % self.num_train]
                    for k in range(batch_size)]
        else:
            rng = rng or np.random
            sids = [self.train_ids[i] for i in rng.randint(0, self.num_train, batch_size)]
        return self._batch(sids)

    def sample_val(self, batch_size, batch_id):
        start = (batch_id * batch_size) % max(self.num_val, 1)
        sids = [self.val_ids[(start + k) % self.num_val] for k in range(batch_size)]
        return self._batch(sids)

    def cache_stats(self) -> dict:
        return self._cache.stats()


class SintelData:
    """MPI-Sintel T-frame sliding-window volumes.

    Layout: `training/<pass>/<clip>/frame_XXXX.png`,
    `training/flow/<clip>/frame_XXXX.flo` (`sintelLoader.py:20-45`). Batches:
    volume (B, H, W, 3T) channel-stacked frames + flows (B, H, W, 2(T-1))
    at native GT resolution (`sintelLoader.py:77-93`). Optional random crop
    to `cfg.crop_size` of the network input (train only, `deepOF.py:14-16`).
    """

    mean = SINTEL_MEAN

    def __init__(self, cfg: DataConfig):
        self.cfg = cfg
        self.t = cfg.time_step
        if cfg.sintel_pair_split_file is not None and self.t != 2:
            raise ValueError(
                "data.sintel_pair_split_file is the gen-1 PAIR split "
                "(`version1/loader/sintelLoader.py:38-70`) and requires "
                f"time_step=2; got time_step={self.t}")
        img_root = os.path.join(cfg.data_path, "training", cfg.sintel_pass)
        flow_root = os.path.join(cfg.data_path, "training", "flow")
        clips = sorted(os.listdir(img_root))
        self.windows: list[list[str]] = []  # absolute frame paths per window
        self.flow_windows: list[list[str]] = []
        val: list[int] = []
        for clip in clips:
            frames = sorted(
                os.path.join(img_root, clip, f)
                for f in os.listdir(os.path.join(img_root, clip))
                if f.endswith(".png")
            )
            flows = sorted(
                os.path.join(flow_root, clip, f)
                for f in os.listdir(os.path.join(flow_root, clip))
                if f.endswith(".flo")
            )
            clip_start = len(self.windows)
            n_windows = len(frames) - self.t + 1
            for s in range(0, n_windows):
                self.windows.append(frames[s : s + self.t])
                self.flow_windows.append(flows[s : s + self.t - 1])
            # Reference val membership, exactly (`sintelLoader.py:47-70`):
            # the first window of every clip, and for bamboo_2 one extra
            # window starting at frame `time_step` (23 clips + 1 = 24).
            if n_windows > 0:
                val.append(clip_start)
            if clip == "bamboo_2" and n_windows > self.t:
                val.append(clip_start + self.t)
        if cfg.sintel_pair_split_file is not None:
            # Gen-1 membership (`sintelLoader.py:47-70`): the k-th line of
            # Sintel_train_val.txt labels the k-th consecutive frame pair
            # in sorted clip x frame order — with time_step=2 that order
            # IS self.windows' construction order. "1" = train, "2" = val.
            with open(cfg.sintel_pair_split_file) as sf:
                labels = [ln.strip()[:1] for ln in sf if ln.strip()]
            if len(labels) != len(self.windows):
                raise ValueError(
                    f"pair split file {cfg.sintel_pair_split_file!r} has "
                    f"{len(labels)} entries but the dataset has "
                    f"{len(self.windows)} consecutive pairs")
            bad = sorted({c for c in labels} - {"1", "2"})
            if bad:
                raise ValueError(
                    f"pair split file {cfg.sintel_pair_split_file!r} has "
                    f"entries {bad}; expected '1' (train) or '2' (val)")
            val = [i for i, c in enumerate(labels) if c == "2"]
        self.val_idx = val
        self.train_idx = [i for i in range(len(self.windows)) if i not in set(self.val_idx)]
        self.num_train, self.num_val = len(self.train_idx), len(self.val_idx)
        self._cache = _DecodedCache(cfg.cache_decoded, _imread_bgr,
                                    max_bytes=cfg.cache_bytes)
        self._flo_hw: tuple[int, int] | None = None  # native path probe
        self._native_ok: bool | None = None  # codec probe, once

    def _window(self, w: int, crop_rng: np.random.RandomState | None) -> tuple[np.ndarray, np.ndarray]:
        imgs = [_resize(self._cache(p), self.cfg.image_size) for p in self.windows[w]]
        vol = np.concatenate(imgs, axis=-1).astype(np.float32)  # (H,W,3T)
        if crop_rng is not None and self.cfg.crop_size is not None:
            ch, cw = self.cfg.crop_size
            h, w_ = vol.shape[:2]
            y = crop_rng.randint(0, h - ch + 1)
            x = crop_rng.randint(0, w_ - cw + 1)
            vol = vol[y : y + ch, x : x + cw]
        flows = np.concatenate(
            [read_flo(p) for p in self.flow_windows[w]], axis=-1
        ).astype(np.float32)  # native res, (H,W,2(T-1))
        return vol, flows

    def _batch(self, idxs, crop_rng=None):
        nb = self._native_batch(idxs, crop_rng)
        if nb is not None:
            return nb
        vols, flows = zip(*(self._window(i, crop_rng) for i in idxs))
        return {"volume": np.stack(vols), "flow": np.stack(flows)}

    def _native_batch(self, idxs, crop_rng=None) -> dict | None:
        """Whole-batch PNG decode + .flo read on the C++ thread pool
        (streaming mode; the decoded cache already amortizes the python
        path). Falls back to the cv2 path when unavailable. Identical
        output to `_window` per sample, including the crop rng draws."""
        from .. import native

        if self.cfg.cache_decoded:
            return None
        frame_paths = [p for i in idxs for p in self.windows[i]]
        if self._native_ok is None:  # probe the build's codecs once
            self._native_ok = (native.available()
                               and native.image_supported(frame_paths[0]))
        if not self._native_ok:
            return None
        t = self.t
        b = len(idxs)
        h, w = self.cfg.image_size
        # all native reads happen BEFORE any crop_rng draw, so a failed
        # batch falls back to `_window` with the rng stream intact (same
        # draw order as the python path)
        try:
            imgs = native.decode_image_batch(frame_paths, (h, w))
            flow_paths = [p for i in idxs for p in self.flow_windows[i]]
            if self._flo_hw is None:
                self._flo_hw = native.flo_dims(flow_paths[0])
            fh, fw = self._flo_hw
            flo = native.read_flo_batch(flow_paths, (fh, fw))
        except (OSError, RuntimeError) as e:
            _warn_native_fallback(e)
            return None
        # channel-stack each window's T frames (frame-major, BGR within)
        vols = (imgs.reshape(b, t, h, w, 3).transpose(0, 2, 3, 1, 4)
                .reshape(b, h, w, 3 * t))
        if crop_rng is not None and self.cfg.crop_size is not None:
            ch, cw = self.cfg.crop_size
            out = np.empty((b, ch, cw, 3 * t), np.float32)
            for k in range(b):  # same rng draw order as _window
                y = crop_rng.randint(0, h - ch + 1)
                x = crop_rng.randint(0, w - cw + 1)
                out[k] = vols[k, y : y + ch, x : x + cw]
            vols = out
        flows = (flo.reshape(b, t - 1, fh, fw, 2).transpose(0, 2, 3, 1, 4)
                 .reshape(b, fh, fw, 2 * (t - 1)))
        return {"volume": vols, "flow": flows}

    def sample_train(self, batch_size, iteration=None, rng=None):
        # no frame-sequential gen-2 mode exists for windows; a
        # sequential (`iteration`) caller still gets a DETERMINISTIC
        # exact-batch_size draw per iteration instead of a silently
        # unseeded one (same contract as the other dataset classes)
        if rng is None:
            rng = np.random.RandomState(iteration)  # None = OS entropy
        idxs = [self.train_idx[i] for i in rng.randint(0, self.num_train, batch_size)]
        return self._batch(idxs, crop_rng=rng)

    def sample_val(self, batch_size, batch_id):
        start = (batch_id * batch_size) % max(self.num_val, 1)
        idxs = [self.val_idx[(start + k) % self.num_val] for k in range(batch_size)]
        return self._batch(idxs)

    def cache_stats(self) -> dict:
        return self._cache.stats()


class UCF101Data:
    """UCF-101 frame pairs for joint flow + action learning.

    Layout: `frames/<class>/<clip>/<frame>.jpg`, clip names
    `v_<Class>_gNN_cMM`; group NN > 7 -> train (`ucf101Loader.py:42-58`).
    Train batch: one random consecutive pair from each of B distinct random
    classes, with the class index as the action label
    (`ucf101Loader.py:66-87`).
    """

    mean = UCF101_MEAN

    def __init__(self, cfg: DataConfig):
        self.cfg = cfg
        root = os.path.join(cfg.data_path, "frames")
        self.classes = sorted(os.listdir(root))
        self.train_clips: dict[int, list[list[str]]] = {}
        self.val_clips: dict[int, list[list[str]]] = {}
        for ci, cls in enumerate(self.classes):
            for clip in sorted(os.listdir(os.path.join(root, cls))):
                frames = sorted(
                    os.path.join(root, cls, clip, f)
                    for f in os.listdir(os.path.join(root, cls, clip))
                )
                if len(frames) < 2:
                    continue
                m = re.search(r"_g(\d+)_", clip)
                group = int(m.group(1)) if m else 99
                (self.train_clips if group > 7 else self.val_clips).setdefault(
                    ci, []
                ).append(frames)
        self.num_train = sum(len(v) for v in self.train_clips.values())
        self.num_val = sum(len(v) for v in self.val_clips.values())
        self._cache = _DecodedCache(cfg.cache_decoded, _imread_bgr,
                                    max_bytes=cfg.cache_bytes)
        self._native_ok: bool | None = None  # codec probe, once

    def _batch_from(self, clips: dict[int, list[list[str]]], class_ids, rng):
        # pick all (src, tgt) frame paths first (one rng draw order shared
        # by the native and python decode paths), then decode the whole
        # batch in one call
        paths, labels = [], []
        for ci in class_ids:
            pool = clips[ci]
            frames = pool[rng.randint(0, len(pool))]
            i = rng.randint(0, len(frames) - 1)
            paths += [frames[i], frames[i + 1]]
            labels.append(ci)
        imgs = self._decode_many(paths)
        return {
            "source": imgs[0::2],
            "target": imgs[1::2],
            "label": np.asarray(labels, np.int32),
        }

    def _decode_many(self, paths: list[str]) -> np.ndarray:
        """(N, H, W, 3) float32 BGR: JPEG decode on the C++ thread pool in
        streaming mode, cv2 + decoded cache otherwise."""
        from .. import native

        if not self.cfg.cache_decoded:
            if self._native_ok is None:  # probe the build's codecs once
                self._native_ok = (native.available()
                                   and native.image_supported(paths[0]))
            if self._native_ok:
                try:
                    return native.decode_image_batch(paths, self.cfg.image_size)
                except (OSError, RuntimeError) as e:
                    _warn_native_fallback(e)
        return np.stack([
            _resize(self._cache(p), self.cfg.image_size) for p in paths
        ]).astype(np.float32)

    def sample_train(self, batch_size, iteration=None, rng=None):
        # sequential callers: deterministic per-iteration draw (see
        # SintelData.sample_train)
        if rng is None:
            rng = np.random.RandomState(iteration)  # None = OS entropy
        avail = list(self.train_clips)
        replace = batch_size > len(avail)
        class_ids = rng.choice(avail, size=batch_size, replace=replace)
        return self._batch_from(self.train_clips, class_ids, rng)

    def sample_val(self, batch_size, batch_id):
        """One batch from a single class — the reference evaluates 101
        class-batches in turn (`ucf101train.py:210-223`)."""
        rng = np.random.RandomState(batch_id)
        avail = sorted(self.val_clips)
        ci = avail[batch_id % len(avail)]
        return self._batch_from(self.val_clips, [ci] * batch_size, rng)

    def cache_stats(self) -> dict:
        return self._cache.stats()


class SyntheticData:
    """Procedural dataset with exact ground-truth flow, for tests and the
    benchmark harness (no counterpart in the reference, which has no tests).

    Each sample: a smooth random image; the target is the source translated
    by a per-sample constant (u, v) — so GT flow is uniform and the
    unsupervised loss is minimized by the true flow. style="affine"
    generalizes to a spatially VARYING exact-GT field (rotation/scale/shear
    about a random center, magnitude bounded by max_shift): the source is
    constructed as the bilinear backward warp of the target canvas by the
    GT field, so the unsupervised objective's minimizer is still exactly
    the GT flow, but a network can no longer satisfy it with a single
    global translation — it must discriminate spatially.
    """

    mean = (0.0, 0.0, 0.0)
    #: bumped whenever the procedural generator's output changes for the
    #: same seed (e.g. the r04 multi-octave canvas rewrite = 2): fitting
    #: tools fingerprint it so a checkpoint lineage never silently
    #: resumes across a data-distribution change.
    CANVAS_VERSION = 2

    def __init__(self, cfg: DataConfig, num_train: int = 64, num_val: int = 16,
                 max_shift: float = 4.0, feature_scale: int = 8,
                 style: str = "noise", n_blobs: int = 8):
        self.cfg = cfg
        self.num_train, self.num_val = num_train, num_val
        self._max_shift = max_shift
        # pixels per random-noise feature: the photometric attraction basin
        # around the true flow is ~ a quarter feature wavelength, so
        # feature_scale must comfortably exceed max_shift for the
        # unsupervised objective to be optimizable from a zero-flow init
        self._feature_scale = feature_scale
        # "noise": upscaled random noise (quasi-periodic — its smoothed
        # autocorrelation has NEGATIVE lobes near the feature scale, so the
        # finest-level photometric gradient at zero flow can point away
        # from the true shift). "blobs": sparse Gaussian blobs on a smooth
        # gradient background — autocorrelation positive and monotone past
        # max_shift at every pyramid level, the optimizable regime for the
        # unsupervised objective.
        self._style = style
        # blob count controls how much of the image carries photometric
        # signal: with few blobs most pixels sit on the smooth background
        # where the aperture problem makes many flows reconstruct equally
        # well (observed: 12k-step runs settle at AEE ~3.9, WORSE than
        # the 3.45 zero-flow baseline, while the loss keeps improving —
        # artifacts/synthetic_fit_long.jsonl). Densify for fitting runs.
        self._n_blobs = n_blobs

    def _sample(self, seed: int, shift_bound: float | None = None
                ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """shift_bound overrides the DISPLACEMENT range only (curriculum
        training, tools/synthetic_fit.py); canvas statistics (blob sigma)
        always follow the constructor's max_shift so the train images
        stay distributionally identical to eval. Integer-shift styles
        quantize the bound to whole pixels (rounded)."""
        rng = np.random.RandomState(seed)
        h, w = self.cfg.image_size
        if self._style == "affine":
            return self._sample_affine(rng, h, w, shift_bound)
        if self._style == "blobs":
            img = self._blob_canvas(rng, h + 16, w + 16)
        else:
            fs = self._feature_scale
            base = rng.rand(h // fs + 2, w // fs + 2, 3).astype(np.float32) * 255.0
            img = cv2.resize(base, (w + 16, h + 16),
                             interpolation=cv2.INTER_CUBIC)
        bound = int(round(self._max_shift if shift_bound is None
                          else shift_bound))
        u, v = rng.randint(-bound, bound + 1, 2)
        src = img[8 : 8 + h, 8 : 8 + w]
        tgt = img[8 + v : 8 + v + h, 8 + u : 8 + u + w]
        # tgt[y, x] == src[y+v, x+u], so source content at p sits at
        # p + (-u, -v) in the target: GT flow (and the minimizer of the
        # backward-warp loss, recon[p] = tgt[p + f] == src[p]) is (-u, -v).
        flow = np.broadcast_to(
            np.asarray([-u, -v], np.float32), (h, w, 2)
        ).copy()
        return src, tgt, flow

    def _sample_affine(self, rng, h: int, w: int,
                       shift_bound: float | None = None):
        """Spatially varying exact-GT pair. GT field g = affine(p - c) + t,
        rescaled so max |g| <= max_shift (or the curriculum's shift_bound
        override — displacement only, canvas untouched). Construction: the
        TARGET is the blob canvas; the SOURCE is the exact bilinear
        backward warp of the target by g (cv2.remap) — i.e.
        src[p] = tgt[p + g(p)] by construction, which is precisely what
        the photometric loss's reconstruction computes, so its minimizer
        is g and AEE-vs-g is an exact learning metric (same convention as
        the shift styles: tgt[p + flow] == src[p])."""
        bound = self._max_shift if shift_bound is None else shift_bound
        tgt = self._blob_canvas(rng, h, w)
        yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
        cy, cx = rng.rand(2) * [h - 1, w - 1]
        # rotation + log-scale + shear, each small; plus translation
        ang = (rng.rand() - 0.5) * 0.2
        scale = 1.0 + (rng.rand() - 0.5) * 0.1
        shear = (rng.rand() - 0.5) * 0.1
        a = np.asarray([[np.cos(ang), -np.sin(ang)],
                        [np.sin(ang), np.cos(ang)]], np.float32)
        a = a @ np.asarray([[scale, shear], [0.0, 1.0 / scale]], np.float32)
        a -= np.eye(2, dtype=np.float32)
        tu, tv = (rng.rand(2) * 2 - 1) * bound * 0.5
        gu = a[0, 0] * (xx - cx) + a[0, 1] * (yy - cy) + tu
        gv = a[1, 0] * (xx - cx) + a[1, 1] * (yy - cy) + tv
        mag = float(np.sqrt(gu**2 + gv**2).max())
        if mag > bound:
            gu *= bound / mag
            gv *= bound / mag
        gu = gu.astype(np.float32)  # tu/tv are python floats -> f64 maps
        gv = gv.astype(np.float32)
        src = cv2.remap(tgt, xx + gu, yy + gv, cv2.INTER_LINEAR,
                        borderMode=cv2.BORDER_REPLICATE)
        flow = np.stack([gu, gv], axis=-1)
        return src.astype(np.float32), tgt, flow

    def _blob_canvas(self, rng, ch: int, cw: int) -> np.ndarray:
        """Smooth linear-gradient background + MULTI-OCTAVE Gaussian blobs:
        sigmas log-spaced from ~max_shift up to ~1/3 of the canvas, so the
        image has structure at every pyramid scale — the property natural
        images (1/f spectra) have and that coarse-to-fine estimation
        depends on. Single-octave blobs (sigma ~ max_shift only, the
        pre-r04 canvas) are invisible once downsampled 2-3 levels, which
        left the coarse pyramid losses featureless and made shifts beyond
        the finest levels' photometric basin unlearnable (DESIGN.md r04
        item 6/7)."""
        yy, xx = np.mgrid[0:ch, 0:cw].astype(np.float32)
        gdir = rng.rand(2) * 2 - 1
        bg = 60.0 + 60.0 * (gdir[0] * yy / ch + gdir[1] * xx / cw + 1.0)
        img = np.repeat(bg[..., None], 3, axis=-1)
        s_lo = max(self._max_shift, 3.0)
        s_hi = max(min(ch, cw) / 3.0, s_lo + 1.0)
        for _ in range(self._n_blobs):
            cy, cx = rng.rand(2) * [ch - 1, cw - 1]
            color = rng.rand(3) * 200.0 - 100.0
            # log-uniform sigma across the octaves; big blobs get muted
            # amplitude (like natural 1/f spectra) so small structure
            # stays visible on top of them
            s = float(np.exp(rng.uniform(np.log(s_lo), np.log(s_hi))))
            amp = (s_lo / s) ** 0.5
            blob = np.exp(-((yy - cy) ** 2 + (xx - cx) ** 2) / (2 * s * s))
            img += blob[..., None] * color * amp
        return np.clip(img, 0.0, 255.0).astype(np.float32)

    def _batch(self, seeds, shift_bound: float | None = None) -> dict:
        srcs, tgts, flows = zip(*(self._sample(int(s), shift_bound)
                                  for s in seeds))
        t = self.cfg.time_step
        out = {
            "source": np.stack(srcs),
            "target": np.stack(tgts),
            "flow": np.stack(flows),
            "label": np.asarray([int(s) % 101 for s in seeds], np.int32),
        }
        if t > 2:  # volume mode: repeat the pair into a T-frame volume
            vol = [out["source"], out["target"]] * ((t + 1) // 2)
            out["volume"] = np.concatenate(vol[:t], axis=-1)
            out["flow"] = np.concatenate([out["flow"]] * (t - 1), axis=-1)
        return out

    def sample_train(self, batch_size, iteration=None, rng=None,
                     max_shift: float | None = None):
        """max_shift overrides the TRAIN displacement range only (shift
        curriculum); canvases and the val split are unaffected."""
        if iteration is not None:
            seeds = [(iteration * batch_size + k) % self.num_train for k in range(batch_size)]
        else:
            rng = rng or np.random
            seeds = rng.randint(0, self.num_train, batch_size)
        return self._batch(seeds, shift_bound=max_shift)

    def sample_val(self, batch_size, batch_id):
        seeds = [self.num_train + (batch_id * batch_size + k) % self.num_val
                 for k in range(batch_size)]
        return self._batch(seeds)

    def cache_stats(self) -> dict:
        """Procedural data decodes nothing; a zeroed record keeps the
        observability schema uniform across datasets."""
        return {"hits": 0, "misses": 0, "evictions": 0, "bytes": 0,
                "entries": 0}


class TokenData:
    """Rows of `seq_len + 1` token ids for a language model, made from a
    fixed seed: `POOL_ROWS` train rows, ids drawn from a Zipf law of
    exponent `ZIPF_EXPONENT` over the vocabulary held (frequent ids repeat,
    as on text) less `lm.mask_token_id`, which the data never draws where
    the family has one, packed with no padding. A batch is {"tokens": int32[b, seq_len + 1]}: position t's
    target is position t + 1's id. Same protocol as the frame datasets, so
    the pipeline, the staging and the `put` are theirs; `mean` is unused."""

    mean = (0.0, 0.0, 0.0)
    POOL_ROWS = 64
    ZIPF_EXPONENT = 1.1

    def __init__(self, cfg: DataConfig, lm, num_val: int = 16, seed: int = 0):
        self.cfg = cfg
        self.num_train, self.num_val = self.POOL_ROWS, num_val
        mask_id = getattr(lm, "mask_token_id", None)
        drawn = lm.vocab_size - (mask_id is not None)
        p = 1.0 / np.arange(1, drawn + 1, dtype=np.float64) ** self.ZIPF_EXPONENT
        cdf = np.cumsum(p / p.sum())
        u = np.random.RandomState(seed).random_sample(
            (self.num_train + num_val, lm.seq_len + 1))
        rows = np.minimum(np.searchsorted(cdf, u), drawn - 1)
        if mask_id is not None:  # the ids from the mask's on move up by one
            rows = rows + (rows >= mask_id)
        self.rows = rows.astype(np.int32)

    def sample_train(self, batch_size, iteration=None, rng=None):
        if iteration is not None:
            idx = (iteration * batch_size + np.arange(batch_size)) % self.num_train
        else:
            idx = (rng or np.random).randint(0, self.num_train, batch_size)
        return {"tokens": self.rows[idx]}

    def sample_val(self, batch_size, batch_id):
        idx = self.num_train + (batch_id * batch_size
                                + np.arange(batch_size)) % self.num_val
        return {"tokens": self.rows[idx]}

    def cache_stats(self) -> dict:
        return {"hits": 0, "misses": 0, "evictions": 0, "bytes": 0,
                "entries": 0}


def build_dataset(cfg: DataConfig, lm=None) -> Dataset:
    """`lm`: the experiment's `lm` section, which sizes the `tokens`
    dataset (vocabulary held, row length); the frame datasets ignore it."""
    builders = {
        "flyingchairs": FlyingChairsData,
        "sintel": SintelData,
        "ucf101": UCF101Data,
        "synthetic": SyntheticData,
        "tokens": lambda c: TokenData(c, lm),
    }
    if cfg.dataset not in builders:
        raise KeyError(f"unknown dataset {cfg.dataset!r}; available: {sorted(builders)}")
    if cfg.dataset == "tokens" and lm is None:
        raise ValueError("dataset 'tokens' is sized by the experiment's `lm` "
                         "section: build_dataset(cfg.data, lm=cfg.lm)")
    return builders[cfg.dataset](cfg)
