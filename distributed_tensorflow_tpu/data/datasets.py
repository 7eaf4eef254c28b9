"""Dataset objects with the reference's input-data semantics.

The reference's input pipeline (``MNISTDist.py:167,178``) is
``input_data.read_data_sets(FLAGS.data_dir, one_hot=True)`` + per-worker
``mnist.train.next_batch(batch_size)``: every worker loads the full dataset
and draws its own independently-shuffled minibatches (no inter-worker
sharding). ``DataSet``/``read_data_sets`` reproduce that API and semantics;
``DataSet.shard`` adds the TPU-idiomatic alternative (disjoint shards for
synchronous data-parallel).

Sources, in priority order:
1. IDX files in ``data_dir`` (what the TF tutorial downloader leaves there)
2. CIFAR-10 python pickle batches in ``data_dir`` (for dataset="cifar10")
3. deterministic procedural fallback (offline environments; see synthetic.py)
"""

from __future__ import annotations

import os
import pickle
from dataclasses import dataclass, field

import numpy as np

from distributed_tensorflow_tpu.data import synthetic
from distributed_tensorflow_tpu.data.idx import find_idx_file, read_idx

_MNIST_FILES = {
    "train_images": "train-images-idx3-ubyte",
    "train_labels": "train-labels-idx1-ubyte",
    "test_images": "t10k-images-idx3-ubyte",
    "test_labels": "t10k-labels-idx1-ubyte",
}

SYNTHETIC_TRAIN = 20000
SYNTHETIC_TEST = 2000
LM_TRAIN = 4096  # lm split sizes: sequences are procedural, fresh-
LM_TEST = 512    # permutation-per-row; memorization is impossible anyway


class DataSet:
    """One split. ``next_batch`` matches the reference tutorial DataSet:
    shuffled epochs, each worker shuffles independently from its seed.

    Images may be float32 (already normalized) or uint8: uint8 storage
    keeps the dataset at 1/4 the memory and batches are assembled on
    demand — through the native C++ gather (distributed_tensorflow_tpu.
    native) when its library is built, else NumPy."""

    def __init__(self, images: np.ndarray, labels: np.ndarray, *, one_hot: bool = True,
                 num_classes: int = 10, seed: int = 0):
        assert images.shape[0] == labels.shape[0]
        if images.dtype == np.uint8:
            self._images_u8 = images.reshape(len(images), -1)
            self._images_f32: np.ndarray | None = None
        else:
            self._images_u8 = None
            self._images_f32 = images
        self.labels_int = labels.astype(np.int64)
        # Fail loudly on out-of-range class ids HERE, at load time: the
        # TPU-form cross-entropy one-hots integer labels, and
        # jax.nn.one_hot maps an invalid id to an all-zero row — a
        # corrupt loader would silently train with those examples
        # dropped from the loss (ADVICE r3). One O(n) host check at
        # construction beats a per-step device check.
        bad = (self.labels_int < 0) | (self.labels_int >= num_classes)
        if bad.any():
            idx = int(np.argmax(bad))
            raise ValueError(
                f"label out of range: labels[{idx}] = "
                f"{int(self.labels_int[idx])} not in [0, {num_classes}) "
                f"({int(bad.sum())} invalid of {len(self.labels_int)})")
        self.one_hot = one_hot
        self.num_classes = num_classes
        self._rng = np.random.default_rng(seed)
        self._order = self._fresh_order(images.shape[0])
        self._pos = 0
        self.epochs_completed = 0

    def _fresh_order(self, n: int) -> np.ndarray:
        """Epoch shuffle order. The permutation itself runs in the native
        C++ data plane (Fisher-Yates, fastdata.cpp) when the library is
        built, NumPy otherwise; each epoch's sub-seed is drawn from this
        DataSet's seeded generator either way, so the stream is
        deterministic per (seed, backend)."""
        from distributed_tensorflow_tpu import native

        sub_seed = int(self._rng.integers(0, 2**63 - 1))
        order = native.permutation(n, sub_seed)
        if order is None:
            order = np.random.default_rng(sub_seed).permutation(n)
        return order

    @property
    def images(self) -> np.ndarray:
        """Full split as float32 in [0,1] (materialized once for u8 storage)."""
        if self._images_f32 is None:
            self._images_f32 = self._images_u8.astype(np.float32) / 255.0
        return self._images_f32

    @property
    def num_examples(self) -> int:
        return len(self.labels_int)

    @property
    def labels(self) -> np.ndarray:
        if self.one_hot:
            out = np.zeros((len(self.labels_int), self.num_classes), np.float32)
            out[np.arange(len(self.labels_int)), self.labels_int] = 1.0
            return out
        return self.labels_int

    def _next_indices(self, batch_size: int) -> np.ndarray:
        """Sequential walk over a shuffled order, reshuffling each epoch —
        the tutorial ``DataSet.next_batch`` index behavior the reference's
        hot loop relies on (``MNISTDist.py:178``)."""
        if self.num_examples == 0:
            raise ValueError("next_batch on an empty DataSet (0 examples)")
        idx = np.empty(batch_size, dtype=np.int64)
        filled = 0
        while filled < batch_size:
            take = min(batch_size - filled, len(self._order) - self._pos)
            idx[filled : filled + take] = self._order[self._pos : self._pos + take]
            self._pos += take
            filled += take
            if self._pos >= len(self._order):
                self._order = self._fresh_order(self.num_examples)
                self._pos = 0
                self.epochs_completed += 1
        return idx

    def next_batch(self, batch_size: int) -> tuple[np.ndarray, np.ndarray]:
        """(float32 images in [0,1], one-hot or int64 labels) — the
        reference tutorial API (``MNISTDist.py:178``)."""
        idx = self._next_indices(batch_size)
        xs = self._gather(idx)
        if self.one_hot:
            ys = None
            if self._images_u8 is not None:
                from distributed_tensorflow_tpu import native

                ys = native.onehot_gather(self.labels_int, idx, self.num_classes)
            if ys is None:
                ys = np.zeros((batch_size, self.num_classes), np.float32)
                ys[np.arange(batch_size), self.labels_int[idx]] = 1.0
        else:
            ys = self.labels_int[idx]
        return xs, ys

    def next_batch_raw(self, batch_size: int) -> tuple[np.ndarray, np.ndarray]:
        """(uint8 images, int32 class ids) — the thin-wire batch format.

        Host->device traffic per example drops from 3176 B (f32 pixels +
        one-hot f32) to 788 B; models normalize on device (uint8 inputs are
        recognized in ``apply``) and the loss/accuracy ops accept integer
        labels. Where the host-to-device input link is the throughput
        ceiling this is the fast path ``bench.py`` and
        ``--raw_input`` use. Same shuffled-epoch index stream as
        ``next_batch``.
        """
        idx = self._next_indices(batch_size)
        return self._raw_u8()[idx], self.labels_int[idx].astype(np.int32)

    def _raw_u8(self) -> np.ndarray:
        if self._images_u8 is not None:
            return self._images_u8  # native u8 source: exact bytes
        if getattr(self, "_u8_cache", None) is None:
            # one-time quantization of float-stored sources (synthetic /
            # CIFAR pickles); kept separate from _images_u8 so the f32
            # next_batch path stays exactly as loaded
            self._u8_cache = np.clip(
                np.round(self._images_f32 * 255.0), 0, 255
            ).astype(np.uint8).reshape(len(self._images_f32), -1)
        return self._u8_cache

    def _gather(self, idx: np.ndarray) -> np.ndarray:
        if self._images_u8 is not None:
            from distributed_tensorflow_tpu import native

            out = native.gather_normalize(self._images_u8, idx)
            if out is not None:
                return out
            return self._images_u8[idx].astype(np.float32) / 255.0
        return self._images_f32[idx]

    def shard(self, index: int, count: int) -> "DataSet":
        """Disjoint contiguous shard — the sync-DP alternative to the
        reference's everyone-loads-everything scheme."""
        sl = slice(index * self.num_examples // count,
                   (index + 1) * self.num_examples // count)
        src = self._images_u8 if self._images_u8 is not None else self._images_f32
        return DataSet(src[sl], self.labels_int[sl], one_hot=self.one_hot,
                       num_classes=self.num_classes, seed=index)


@dataclass
class Datasets:
    train: DataSet
    test: DataSet
    validation: DataSet | None = None
    source: str = "synthetic"  # "idx" | "cifar" | "synthetic"
    meta: dict = field(default_factory=dict)


def _load_mnist_idx(data_dir: str) -> dict[str, np.ndarray] | None:
    paths = {k: find_idx_file(data_dir, v) for k, v in _MNIST_FILES.items()}
    if not all(paths.values()):
        return None

    def _read(p: str) -> np.ndarray:
        from distributed_tensorflow_tpu import native

        arr = native.read_idx_u8(p)  # fast path: uncompressed u8 via C++
        return arr if arr is not None else read_idx(p)

    return {k: _read(p) for k, p in paths.items()}


def _load_cifar10(data_dir: str):
    """CIFAR-10 python-version pickle batches (data_batch_1..5, test_batch)."""
    def _find(name):
        for root in (data_dir, os.path.join(data_dir, "cifar-10-batches-py")):
            p = os.path.join(root, name)
            if os.path.exists(p):
                return p
        return None

    train_paths = [_find(f"data_batch_{i}") for i in range(1, 6)]
    test_path = _find("test_batch")
    if not all(train_paths) or test_path is None:
        return None

    def _read(p):
        with open(p, "rb") as f:
            d = pickle.load(f, encoding="bytes")
        x = d[b"data"].reshape(-1, 3, 32, 32).transpose(0, 2, 3, 1)
        return x.astype(np.float32) / 255.0, np.asarray(d[b"labels"], np.int64)

    xs, ys = zip(*[_read(p) for p in train_paths])
    tx, ty = _read(test_path)
    return np.concatenate(xs), np.concatenate(ys), tx, ty


def read_data_sets(
    data_dir: str,
    one_hot: bool = True,
    dataset: str = "mnist",
    seed: int = 0,
    validation_size: int = 0,
    seq_len: int = 256,
    vocab_size: int = 64,
    reserved_ids: int = 0,
) -> Datasets:
    """API parity with the tutorial loader the reference imports
    (``MNISTDist.py:11,167``), extended with ``dataset`` selection:
    "mnist" | "fashion_mnist" (same IDX format) | "cifar10" | "lm"
    (procedural associative-recall token sequences for the causal-LM
    family; ``seq_len``/``vocab_size`` apply only there, and
    ``reserved_ids``: the vocabulary's last ids the tokens leave out).
    Falls back to procedural data when files are absent (offline envs)."""
    dataset = dataset.lower().replace("-", "_")
    if dataset == "lm":
        from distributed_tensorflow_tpu.data.lm import LMDataSet

        train = LMDataSet(LM_TRAIN, seq_len, vocab_size, seed=seed,
                          reserved_ids=reserved_ids)
        test = LMDataSet(LM_TEST, seq_len, vocab_size, seed=seed + 10_000,
                         reserved_ids=reserved_ids)
        val = None
        if validation_size:
            # generated independently (own seed space), not carved from a
            # finite split — any positive size works
            if validation_size < 0:
                raise ValueError(
                    f"validation_size={validation_size} must be >= 0")
            val = LMDataSet(validation_size, seq_len, vocab_size,
                            seed=seed + 20_000, reserved_ids=reserved_ids)
        return Datasets(
            train=train, test=test, validation=val, source="synthetic",
            meta={"kind": "lm", "seq_len": seq_len,
                  "vocab_size": vocab_size,
                  "num_classes": vocab_size},
        )
    if dataset in ("mnist", "fashion_mnist"):
        raw = _load_mnist_idx(data_dir) if data_dir and os.path.isdir(data_dir) else None
        if raw is not None:
            # keep u8 storage: batches normalize on demand (native gather)
            trx = raw["train_images"].reshape(-1, 784)
            trl = raw["train_labels"].astype(np.int64)
            tex = raw["test_images"].reshape(-1, 784)
            tel = raw["test_labels"].astype(np.int64)
            source = "idx"
        else:
            trx, trl = synthetic.synthetic_digits(SYNTHETIC_TRAIN, seed=seed)
            tex, tel = synthetic.synthetic_digits(SYNTHETIC_TEST, seed=seed + 1)
            source = "synthetic"
        meta = {"image_size": 28, "channels": 1, "num_classes": 10, "flat": True}
    elif dataset == "cifar10":
        raw = _load_cifar10(data_dir) if data_dir and os.path.isdir(data_dir) else None
        if raw is not None:
            trx, trl, tex, tel = raw
            source = "cifar"
        else:
            trx, trl = synthetic.synthetic_cifar(SYNTHETIC_TRAIN, seed=seed)
            tex, tel = synthetic.synthetic_cifar(SYNTHETIC_TEST, seed=seed + 1)
            source = "synthetic"
        meta = {"image_size": 32, "channels": 3, "num_classes": 10, "flat": False}
    else:
        raise ValueError(f"unknown dataset {dataset!r}")

    val = None
    if validation_size:
        if not 0 <= validation_size < len(trx):
            raise ValueError(
                f"validation_size={validation_size} must be in "
                f"[0, {len(trx)}) for this train split"
            )
        val = DataSet(trx[:validation_size], trl[:validation_size],
                      one_hot=one_hot, seed=seed + 2)
        trx, trl = trx[validation_size:], trl[validation_size:]

    return Datasets(
        train=DataSet(trx, trl, one_hot=one_hot, seed=seed),
        test=DataSet(tex, tel, one_hot=one_hot, seed=seed + 1),
        validation=val,
        source=source,
        meta=meta,
    )
