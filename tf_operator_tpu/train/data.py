"""Input pipeline: host-side datasets with device prefetch.

The reference delegates data loading entirely to user containers (SURVEY.md
§5: the operator never touches tensors; `tf.data` came with TensorFlow).
A complete TPU framework has to supply the analogue itself: if the host
hands the device one batch at a time synchronously, every step eats a
host→HBM transfer on its critical path. ``DeviceLoader`` pipelines that
away — a background thread stages the next batches onto the device (with
the job's batch sharding) while the current step runs, so steps dequeue
device-resident arrays. This is the jit-era equivalent of TPU infeed /
`tf.data` prefetch-to-device.

Multi-host: each process stages only its addressable shard
(`jax.make_array_from_process_local_data`), so a dp=16 job moves 1/16th
of the global batch per host — the loader contract is "every process
iterates the same dataset structure; each sees its local slice".
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, Iterable, Iterator, Optional

import numpy as np

__all__ = [
    "SyntheticImages",
    "SyntheticTokens",
    "ArrayDataset",
    "DeviceLoader",
    "local_loader",
    "read_idx",
    "write_idx",
    "MnistIdxDataset",
    "TokenMemmapDataset",
    "write_token_corpus",
    "augment_images",
    "AugmentedImages",
    "prepare_classification_images",
    "elastic_global_order",
    "elastic_rank_positions",
    "elastic_coverage",
]


class ArrayDataset:
    """Finite in-memory dataset: yields dict batches sliced from arrays.

    arrays: pytree-of-ndarray with a common leading (example) dim.
    Deterministic order per epoch index (reshuffled by ``seed + epoch``),
    dropping the ragged tail batch (static shapes — XLA recompiles on any
    shape change, SURVEY §6 submit→first-step budget)."""

    def __init__(self, arrays: Any, batch_size: int, *, shuffle: bool = True,
                 seed: int = 0) -> None:
        import jax

        leaves = jax.tree_util.tree_leaves(arrays)
        if not leaves:
            raise ValueError("ArrayDataset needs at least one array")
        n = leaves[0].shape[0]
        for leaf in leaves:
            if leaf.shape[0] != n:
                raise ValueError("all arrays must share the leading dim")
        if batch_size > n:
            raise ValueError(f"batch_size {batch_size} > dataset size {n}")
        self.arrays = arrays
        self.n = n
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.seed = seed

    def __len__(self) -> int:
        return self.n // self.batch_size

    def epoch(self, epoch: int = 0) -> Iterator[Any]:
        import jax

        order = np.arange(self.n)
        if self.shuffle:
            np.random.default_rng(self.seed + epoch).shuffle(order)
        for i in range(len(self)):
            idx = order[i * self.batch_size : (i + 1) * self.batch_size]
            yield jax.tree_util.tree_map(lambda a: a[idx], self.arrays)

    def __iter__(self) -> Iterator[Any]:
        epoch = 0
        while True:  # repeat forever; the consumer bounds steps
            yield from self.epoch(epoch)
            epoch += 1


class SyntheticImages(ArrayDataset):
    """Deterministic fake image-classification data (ImageNet-shaped by
    default) — the benchmarking stand-in the BASELINE configs train on."""

    def __init__(self, batch_size: int, *, n: int = 1024, image_size: int = 224,
                 channels: int = 3, num_classes: int = 1000, seed: int = 0) -> None:
        rng = np.random.default_rng(seed)
        super().__init__(
            {
                "image": rng.standard_normal(
                    (n, image_size, image_size, channels), dtype=np.float32
                ),
                "label": rng.integers(0, num_classes, (n,), dtype=np.int32),
            },
            batch_size,
            seed=seed,
        )


class SyntheticTokens(ArrayDataset):
    """Deterministic fake LM token data."""

    def __init__(self, batch_size: int, *, n: int = 2048, seq_len: int = 512,
                 vocab: int = 32000, seed: int = 0) -> None:
        rng = np.random.default_rng(seed)
        super().__init__(
            {"tokens": rng.integers(0, vocab, (n, seq_len), dtype=np.int32)},
            batch_size,
            seed=seed,
        )


# ---------------------------------------------------------------------------
# Disk-backed readers: MNIST idx-ubyte + tokenized-corpus memmap
# ---------------------------------------------------------------------------

# once-only latch for the native-dataops-unavailable warning in
# _augment_native (must exist at module scope: the warning path is the
# first reader, on hosts where the C++ build fails)
_dataops_warned = False

_IDX_DTYPES = {
    0x08: np.uint8, 0x09: np.int8, 0x0B: np.int16,
    0x0C: np.int32, 0x0D: np.float32, 0x0E: np.float64,
}
_IDX_CODES = {np.dtype(v): k for k, v in _IDX_DTYPES.items()}


def read_idx(path: str) -> np.ndarray:
    """Read an idx-ubyte file (the MNIST wire format the reference's
    dist_mnist consumes via read_data_sets,
    /root/reference/test/e2e/dist-mnist/dist_mnist.py:214-215): 2 zero
    bytes, dtype code, ndim, big-endian uint32 dims, raw data. ``.gz``
    paths decompress transparently (the distribution format)."""
    import gzip

    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        header = f.read(4)
        if len(header) != 4 or header[0] != 0 or header[1] != 0:
            raise ValueError(f"{path}: not an idx file (bad magic {header!r})")
        code, ndim = header[2], header[3]
        if code not in _IDX_DTYPES:
            raise ValueError(f"{path}: unknown idx dtype code 0x{code:02x}")
        dims = np.frombuffer(f.read(4 * ndim), dtype=">u4")
        if dims.size != ndim:
            raise ValueError(f"{path}: truncated idx header")
        data = np.frombuffer(f.read(), dtype=np.dtype(_IDX_DTYPES[code]).newbyteorder(">"))
        n = int(np.prod(dims)) if ndim else 0
        if data.size != n:
            raise ValueError(f"{path}: expected {n} elements, got {data.size}")
        return data.reshape(tuple(int(d) for d in dims)).astype(_IDX_DTYPES[code])


def write_idx(path: str, array: np.ndarray) -> None:
    """Write an idx file (gzip when path ends .gz) — the test/tooling side
    of read_idx, so fixtures carry the real wire format."""
    import gzip

    arr = np.ascontiguousarray(array)
    code = _IDX_CODES.get(arr.dtype)
    if code is None:
        raise ValueError(f"unsupported idx dtype {arr.dtype}")
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "wb") as f:
        f.write(bytes([0, 0, code, arr.ndim]))
        f.write(np.asarray(arr.shape, dtype=">u4").tobytes())
        f.write(arr.astype(arr.dtype.newbyteorder(">")).tobytes())


def _find_idx(data_dir: str, names) -> str:
    import os

    for name in names:
        for suffix in ("", ".gz"):
            p = os.path.join(data_dir, name + suffix)
            if os.path.exists(p):
                return p
    raise FileNotFoundError(
        f"none of {list(names)} (or .gz) under {data_dir}"
    )


class MnistIdxDataset(ArrayDataset):
    """Disk-backed image classification from standard idx files.

    Looks for the canonical MNIST names (train-images-idx3-ubyte /
    train-labels-idx1-ubyte, t10k-* for split="test", optionally .gz) —
    drop the real MNIST distribution files in ``data_dir`` and this
    trains actual MNIST, matching the reference's dist_mnist e2e. Images
    normalize to [0, 1] f32; the per-image shape is whatever the file
    carries (28x28 for MNIST; the e2e fixtures write real scanned-digit
    images at 8x8).

    ``process_shard``: in a multi-process gang each process takes a
    disjoint stride of the examples (rank::nprocs), so shards carry
    distinct real data — the reader-side analogue of what local_loader
    does for synthetic seeds."""

    def __init__(self, data_dir: str, batch_size: int, *, split: str = "train",
                 shuffle: bool = True, seed: int = 0,
                 process_shard: bool = True) -> None:
        prefix = {"train": "train", "test": "t10k"}[split]
        images = read_idx(
            _find_idx(data_dir, (f"{prefix}-images-idx3-ubyte", f"{prefix}-images.idx3-ubyte"))
        )
        labels = read_idx(
            _find_idx(data_dir, (f"{prefix}-labels-idx1-ubyte", f"{prefix}-labels.idx1-ubyte"))
        )
        if images.shape[0] != labels.shape[0]:
            raise ValueError(
                f"{data_dir}: {images.shape[0]} images vs {labels.shape[0]} labels"
            )
        # Dtype-derived scale, NOT per-split max: max-based scaling would
        # normalize train and test differently whenever their brightest
        # pixels differ, silently skewing eval accuracy.
        scale = 255.0 if np.issubdtype(images.dtype, np.integer) else 1.0
        x = images.astype(np.float32) / scale
        y = labels.astype(np.int32)
        # Pre-shard (global) example count: every process must derive the
        # SAME steps-per-epoch from it — rank-local shard sizes differ by
        # one when nprocs doesn't divide n, and a step count read off the
        # local shard would deadlock the gang (one rank dispatching an
        # SPMD step the others never join).
        self.global_n = x.shape[0]
        if process_shard:
            import jax

            rank, n = jax.process_index(), jax.process_count()
            if n > 1:
                x, y = x[rank::n], y[rank::n]
        super().__init__({"image": x, "label": y}, batch_size,
                         shuffle=shuffle, seed=seed)


# ---------------------------------------------------------------------------
# Host-side image augmentation (the ResNet/ImageNet-recipe half the
# synthetic paths never needed: random crop + horizontal flip)
# ---------------------------------------------------------------------------


def augment_images(images: np.ndarray, rng: np.random.Generator, *,
                   pad: int = 4, flip: bool = True,
                   native: Optional[bool] = None) -> np.ndarray:
    """Random-crop + horizontal-flip augmentation, host-side.

    The standard small-image recipe (ResNet/CIFAR): zero-pad ``pad``
    pixels on each spatial edge, crop back to the original h×w at a
    per-image random offset, then mirror each image left-right with
    probability 1/2 (``flip=False`` for orientation-sensitive classes —
    digits/text). images: [b, h, w] or [b, h, w, c]; same shape out.

    Runs on the host on purpose: augmentation is per-example branchy work
    the DeviceLoader's prefetch thread hides behind the step, and keeping
    it off the device keeps the train step's compiled program static.

    Dispatch: the RANDOMNESS is always drawn here (numpy Generator, one
    draw order regardless of path — outputs are bit-identical for one
    seed), and the gather work runs through the native dataops library
    (native/dataops.cc: threaded memcpy crop + in-write flip) when
    ``native`` is None/True, falling back to the numpy loop when the
    library is unavailable or the array layout is unsupported
    (``native=False`` forces the fallback; True raises if unusable)."""
    b, h, w = images.shape[:3]
    if not pad and not flip:
        return images  # no-op config: input returned as-is on EVERY path
    dy = dx = do = None
    if pad:
        dy = rng.integers(0, 2 * pad + 1, b)
        dx = rng.integers(0, 2 * pad + 1, b)
    if flip:
        do = rng.random(b) < 0.5
    if native is not False and b > 0:
        out = _augment_native(images, pad, dy, dx, do)
        if out is not None:
            return out
        if native:
            raise RuntimeError("native augmentation unavailable for this input")
    out = images
    if pad:
        widths = [(0, 0), (pad, pad), (pad, pad)] + [(0, 0)] * (images.ndim - 3)
        padded = np.pad(images, widths)
        out = np.empty_like(images)
        for i in range(b):  # host-side; hidden by the loader's prefetch
            out[i] = padded[i, dy[i]:dy[i] + h, dx[i]:dx[i] + w]
    if flip:
        out = np.where(
            do.reshape((b,) + (1,) * (images.ndim - 1)), out[:, :, ::-1], out
        )
    return out


def _augment_native(images: np.ndarray, pad: int, dy, dx, do) -> Optional[np.ndarray]:
    """Run the crop/flip gather through native/dataops.cc. Returns None
    when the native path cannot serve this input (library missing/broken,
    non-C-contiguous array) so the caller falls back — same offsets, same
    output bytes either way."""
    import ctypes

    global _dataops_warned
    # A failed load already warned once — don't re-run the (subprocess,
    # up-to-120s) native build attempt on every batch of a job that is
    # going to fall back to numpy anyway.
    if _dataops_warned:
        return None
    try:
        from tf_operator_tpu.runtime.native import load_dataops

        lib = load_dataops()
    except Exception as exc:
        # Warn ONCE: the numpy fallback is ~6x slower (BASELINE.md) — at
        # ResNet rates it cannot feed the step, and without a diagnostic
        # an input-bound job points at nothing.
        if not _dataops_warned:
            _dataops_warned = True
            import warnings

            warnings.warn(
                f"native dataops unavailable ({exc!r}); augmentation falls "
                "back to the ~6x-slower numpy path", RuntimeWarning)
        return None
    arr = images if images.flags["C_CONTIGUOUS"] else None
    if arr is None:
        return None
    b, h, w = arr.shape[:3]
    # fold trailing dims + element size into bytes-per-pixel (the op is
    # pure byte movement, dtype-agnostic)
    pixel = arr.itemsize
    for dim in arr.shape[3:]:
        pixel *= dim
    out = np.empty_like(arr)
    # staging arrays must stay referenced across the call (ctypes keeps no
    # reference; a GC'd temp would hand C a dangling pointer)
    dy_a = np.ascontiguousarray(dy, dtype=np.int32) if dy is not None else None
    dx_a = np.ascontiguousarray(dx, dtype=np.int32) if dx is not None else None
    do_a = np.ascontiguousarray(do, dtype=np.uint8) if do is not None else None
    rc = lib.tpuj_augment(
        arr.ctypes.data_as(ctypes.c_void_p),
        out.ctypes.data_as(ctypes.c_void_p),
        b, h, w, pixel, pad,
        dy_a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)) if dy_a is not None else None,
        dx_a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)) if dx_a is not None else None,
        do_a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)) if do_a is not None else None,
        0,
    )
    if rc != 0:
        return None
    return out


class AugmentedImages:
    """Wraps a dict-batch image iterable with augment_images on the
    ``key`` leaf (fresh randomness per batch, deterministic per seed).
    Sits between a disk reader and the DeviceLoader:

        DeviceLoader(AugmentedImages(MnistIdxDataset(...)), sharding)
    """

    def __init__(self, source: Iterable[Any], *, pad: int = 4,
                 flip: bool = True, seed: int = 0, key: str = "image") -> None:
        self.source = source
        self.pad = pad
        self.flip = flip
        self.key = key
        # ONE rng for the object's lifetime (not per-__iter__): re-seeding
        # each epoch would replay identical "random" crops/flips every
        # epoch, defeating the augmentation.
        self._rng = np.random.default_rng(seed)

    def __iter__(self) -> Iterator[Any]:
        for batch in self.source:
            batch = dict(batch)
            batch[self.key] = augment_images(
                batch[self.key], self._rng, pad=self.pad, flip=self.flip
            )
            yield batch


def prepare_classification_images(images: np.ndarray,
                                  image_size: Optional[int] = None) -> np.ndarray:
    """Adapt reader output to a convnet's [b, h, w, 3] contract:
    grayscale [b, h, w] gets a broadcast channel dim, and ``image_size``
    (must be an integer multiple of the native size) upsamples
    nearest-neighbor — e.g. the 8×8 scanned-digit fixtures to 32×32 so a
    /32-downsampling ResNet keeps a spatial cell at the head."""
    if images.ndim == 3:
        images = np.repeat(images[..., None], 3, axis=-1)
    if image_size and image_size != images.shape[1]:
        factor, rem = divmod(image_size, images.shape[1])
        if rem or factor < 1:
            raise ValueError(
                f"image_size {image_size} is not an integer multiple of the "
                f"native size {images.shape[1]}"
            )
        images = np.repeat(np.repeat(images, factor, axis=1), factor, axis=2)
    return images


# ---------------------------------------------------------------------------
# Elastic re-carve primitives (r12)
#
# The classic multi-host carve is ``windows[rank::nprocs]`` — a WORLD-SIZE-
# DEPENDENT stride: change nprocs and every rank's stream silently shifts,
# duplicating some windows and dropping others. Elastic gangs need the
# opposite invariant: one CANONICAL, world-size-independent global order G
# over all windows, plus a pure function from (consumed offset, rank, world
# size) to the windows a rank owns. Then a resize is just "survivors resume
# carving G from the global consumed offset with the new world size" — and
# the union of all rank streams across any shrink→grow→shrink sequence is
# exactly G[0:T], no token duplicated or dropped (tests/test_data_recarve.py
# pins this).
#
# Offset accounting is POSITION-based, not step-based: the global offset C
# counts how many positions of G the gang has consumed in total. During an
# epoch with world size n starting at offset C0, rank r owns positions
# C0+r, C0+r+n, C0+r+2n, ... — one position per rank per "deal row", so a
# gang that completes k rows advances C by k*n atomically.
# ---------------------------------------------------------------------------


def elastic_global_order(n_windows: int, seed: int = 0,
                         shuffle: bool = True) -> np.ndarray:
    """The canonical global window order G: a deterministic permutation of
    ``arange(n_windows)`` seeded by ``seed`` alone — independent of world
    size, rank, and epoch, so every member of every incarnation of an
    elastic gang derives the identical sequence."""
    order = np.arange(int(n_windows))
    if shuffle:
        np.random.default_rng(int(seed)).shuffle(order)
    return order


def elastic_rank_positions(start: int, end: int, rank: int,
                           world_size: int) -> range:
    """Positions of G that ``rank`` (of ``world_size``) owns within the
    half-open offset interval [start, end) — the ``rank::n`` stride
    re-anchored at the global consumed offset. The union over ranks is
    exactly range(start, end); disjointness and coverage are structural."""
    if world_size < 1:
        raise ValueError(f"world_size must be >= 1, got {world_size}")
    if not 0 <= rank < world_size:
        raise ValueError(f"rank {rank} outside world of {world_size}")
    return range(int(start) + int(rank), int(end), int(world_size))


def elastic_coverage(segments) -> list:
    """Flatten a resize history into every (position, rank) assignment.

    ``segments``: iterable of ``(start, end, world_size)`` — one entry per
    resize epoch, offsets half-open and contiguous. Returns the list of
    (position, rank) pairs in position order; the positions are
    range(first start, last end) each exactly once, whatever the world
    sizes were. The verification half of the re-carve contract (used by
    the elastic soak checker and the recarve tests)."""
    out = []
    for start, end, n in segments:
        for r in range(int(n)):
            for p in elastic_rank_positions(start, end, r, n):
                out.append((p, r))
    out.sort(key=lambda pr: pr[0])
    return out


def write_token_corpus(path: str, tokens: np.ndarray, dtype=np.uint16) -> None:
    """Persist a 1-D token stream as a raw little-endian memmap file plus a
    sidecar ``path + '.meta'`` (dtype + count) so readers need no guessing."""
    arr = np.ascontiguousarray(tokens, dtype=dtype)
    arr.tofile(path)
    with open(path + ".meta", "w") as f:
        f.write(f"{np.dtype(dtype).name} {arr.size}\n")


class TokenMemmapDataset:
    """Tokenized-corpus reader: a flat memmapped token stream cut into
    non-overlapping [seq_len] windows, batched — the standard pretraining
    layout (tokenize once offline, train from the memmap; the file never
    loads into RAM). Yields {"tokens": [batch, seq_len] int32} forever,
    reshuffling window order per epoch.

    ``process_shard``: each process reads a disjoint stride of windows
    (rank::nprocs) for multi-host training.

    ``holdout``/``split`` (r5, VERDICT r4 #4): ``holdout=N`` reserves the
    LAST N windows of the corpus as a held-out split carved out BEFORE
    process-sharding, so it is disjoint from every trainer rank's stride
    by construction. split="train" (default) reads everything before the
    reservation; split="holdout" reads exactly the reserved windows — the
    evaluator's view. Trainer and evaluator agree on the boundary by
    sharing the same ``holdout_windows`` workload key."""

    def __init__(self, path: str, batch_size: int, seq_len: int, *,
                 dtype=None, shuffle: bool = True, seed: int = 0,
                 process_shard: bool = True, holdout: int = 0,
                 split: str = "train") -> None:
        import os

        if dtype is None:
            meta = path + ".meta"
            if os.path.exists(meta):
                with open(meta) as f:
                    dtype = np.dtype(f.read().split()[0])
            else:
                dtype = np.uint16
        self._mm = np.memmap(path, dtype=dtype, mode="r")
        n_windows = self._mm.size // seq_len
        if n_windows < 1:
            raise ValueError(
                f"{path}: {self._mm.size} tokens < one window of {seq_len}"
            )
        if split not in ("train", "holdout"):
            raise ValueError(f'unknown split {split!r}; use "train"|"holdout"')
        if split == "holdout" and not holdout:
            raise ValueError('split="holdout" requires holdout > 0')
        if holdout and holdout >= n_windows:
            raise ValueError(
                f"holdout {holdout} >= {n_windows} corpus windows — nothing "
                "left to train on"
            )
        self._windows = np.arange(n_windows)
        if holdout:
            self._windows = (
                self._windows[-holdout:] if split == "holdout"
                else self._windows[:-holdout]
            )
        # Pre-shard (post-holdout) window set: the domain of the elastic
        # canonical order (elastic_batches) — must be identical on every
        # rank at every world size, so it is captured BEFORE the
        # world-size-dependent rank::n carve below.
        self._global_windows = self._windows
        if process_shard:
            import jax

            rank, n = jax.process_index(), jax.process_count()
            if n > 1:
                self._windows = self._windows[rank::n]
        if batch_size > self._windows.size:
            raise ValueError(
                f"batch_size {batch_size} > {self._windows.size} local windows"
            )
        self.batch_size = batch_size
        self.seq_len = seq_len
        self.shuffle = shuffle
        self.seed = seed

    def __len__(self) -> int:
        return self._windows.size // self.batch_size

    def epoch(self, epoch: int = 0) -> Iterator[Any]:
        order = self._windows.copy()
        if self.shuffle:
            np.random.default_rng(self.seed + epoch).shuffle(order)
        for i in range(len(self)):
            idx = order[i * self.batch_size : (i + 1) * self.batch_size]
            batch = np.stack(
                [self._mm[w * self.seq_len : (w + 1) * self.seq_len] for w in idx]
            )
            yield {"tokens": batch.astype(np.int32)}

    def __iter__(self) -> Iterator[Any]:
        epoch = 0
        while True:
            yield from self.epoch(epoch)
            epoch += 1

    # -- elastic re-carve (r12) -------------------------------------------

    def elastic_windows(self, start: int, end: int, rank: int,
                        world_size: int) -> np.ndarray:
        """This rank's window ids for offset interval [start, end) of the
        canonical global order — the re-carve seam: after a resize the
        caller re-invokes this with the new (rank, world_size) anchored at
        the global consumed offset, and token accounting stays exact
        (union over ranks and segments == the uninterrupted stream)."""
        order = elastic_global_order(
            self._global_windows.size, seed=self.seed, shuffle=self.shuffle
        )
        positions = np.fromiter(
            elastic_rank_positions(start, end, rank, world_size), dtype=np.int64
        )
        return self._global_windows[order[positions]] if positions.size else positions

    def elastic_batches(self, start: int, end: int, rank: int,
                        world_size: int) -> Iterator[Any]:
        """Batched view of :meth:`elastic_windows` (drops the ragged tail
        like :meth:`epoch` — callers that need exact accounting consume
        window-granular via elastic_windows)."""
        wins = self.elastic_windows(start, end, rank, world_size)
        for i in range(wins.size // self.batch_size):
            idx = wins[i * self.batch_size : (i + 1) * self.batch_size]
            batch = np.stack(
                [self._mm[w * self.seq_len : (w + 1) * self.seq_len] for w in idx]
            )
            yield {"tokens": batch.astype(np.int32)}


def local_loader(
    dataset_cls: Callable[..., "ArrayDataset"],
    global_batch: int,
    sharding: Any,
    *,
    min_examples: int = 32,
    prefetch: int = 2,
    skip: int = 0,
    **dataset_kw: Any,
) -> "DeviceLoader":
    """The multi-host stream contract in one place: split ``global_batch``
    across processes (must divide), seed the synthetic dataset by rank so
    shards carry distinct data, and wrap it in a prefetching DeviceLoader.
    ``skip`` fast-forwards past batches a previous incarnation already
    trained on (pass the resumed step count on restart-based recovery).
    Used by the lm/resnet workloads' ``data: "stream"`` paths."""
    import jax

    n_proc = jax.process_count()
    if global_batch % n_proc:
        raise ValueError(
            f"batch_size {global_batch} not divisible by {n_proc} processes"
        )
    local = global_batch // n_proc
    ds = dataset_cls(
        local,
        n=max(2 * local, min_examples),
        seed=jax.process_index(),
        **dataset_kw,
    )
    return DeviceLoader(ds, sharding, prefetch=prefetch, skip=skip)


class DeviceLoader:
    """Wraps a host batch iterable; yields device-resident sharded batches.

    A daemon thread pulls host batches, shards them onto the mesh, and
    keeps up to ``prefetch`` staged ahead of the consumer — transfer for
    step N+1 overlaps compute for step N. ``sharding`` is typically
    ``trainer.batch_sharding``; a pytree batch may also map to a pytree
    of shardings (dict batches get the one sharding on every leaf).

    Iteration ends when the source iterator does (pass a bounded iterable
    for epochs; ArrayDataset repeats forever). ``close()`` (or `with`)
    stops the stager; the thread also exits if the consumer drops the
    loader. Errors in the source re-raise at the consumer's next pull.

    How well the prefetch hides the input pipeline is counted as it
    happens: ``batches`` handed out, ``wait_s`` the consumer spent
    blocked in ``next()``, ``empty_pulls`` of them that found nothing
    staged. Under a profiler session each pull is a ``train.data_wait``
    span (``queued`` = batches staged at the pull) and each transfer on
    the stager thread a ``train.data_stage`` span."""

    _END = object()

    def __init__(
        self,
        source: Iterable[Any],
        sharding: Any,
        *,
        prefetch: int = 2,
        skip: int = 0,
        put: Optional[Callable[[Any, Any], Any]] = None,
    ) -> None:
        if prefetch < 1:
            raise ValueError("prefetch must be >= 1")
        if skip < 0:
            raise ValueError("skip must be >= 0")
        self.sharding = sharding
        self._skip = skip
        self._put = put or self._default_put
        self._q: "queue.Queue" = queue.Queue(maxsize=prefetch)
        self._stop = threading.Event()
        self._err: Optional[BaseException] = None
        self.batches = 0
        self.wait_s = 0.0
        self.empty_pulls = 0
        self._thread = threading.Thread(
            target=self._stage, args=(iter(source),), name="device-loader", daemon=True
        )
        self._thread.start()

    def _default_put(self, batch: Any, sharding: Any) -> Any:
        import jax

        if isinstance(sharding, jax.sharding.Sharding):
            shardings = jax.tree_util.tree_map(lambda _: sharding, batch)
        else:  # a pytree of shardings matching the batch structure
            shardings = sharding
        if jax.process_count() > 1:
            # Each process holds its local slice of the global batch;
            # assemble the logically-global arrays from local data.
            return jax.tree_util.tree_map(
                lambda a, s: jax.make_array_from_process_local_data(s, a),
                batch,
                shardings,
            )
        return jax.device_put(batch, shardings)

    def _stage(self, it: Iterator[Any]) -> None:
        from jax.profiler import TraceAnnotation

        try:
            # Restart fast-forward: drop already-consumed batches on the
            # host (no staging cost) so a resumed job continues the stream
            # where the previous incarnation left off.
            try:
                for _ in range(self._skip):
                    next(it)
            except StopIteration:
                self._enqueue_end()
                return
            for batch in it:
                if self._stop.is_set():
                    return
                with TraceAnnotation("train.data_stage"):
                    staged = self._put(batch, self.sharding)
                while not self._stop.is_set():
                    try:
                        self._q.put(staged, timeout=0.2)
                        break
                    except queue.Full:
                        continue
                if self._stop.is_set():
                    return
            self._enqueue_end()
        except BaseException as exc:  # surfaced to the consumer
            self._err = exc
            self._enqueue_end()

    def _enqueue_end(self) -> None:
        while not self._stop.is_set():
            try:
                self._q.put(self._END, timeout=0.2)
                return
            except queue.Full:
                continue

    def __iter__(self) -> Iterator[Any]:
        return self

    def __next__(self) -> Any:
        from jax.profiler import TraceAnnotation

        queued = self._q.qsize()
        t0 = time.perf_counter()
        with TraceAnnotation("train.data_wait", queued=queued):
            while True:
                try:
                    item = self._q.get(timeout=0.2)
                    break
                except queue.Empty:
                    if not self._thread.is_alive() and self._q.empty():
                        item = self._END
                        break
        self.wait_s += time.perf_counter() - t0
        self.empty_pulls += queued == 0
        if item is self._END:
            self._stop.set()
            if self._err is not None:
                err, self._err = self._err, None
                raise err
            raise StopIteration
        self.batches += 1
        return item

    def close(self) -> None:
        self._stop.set()
        # drain so a blocked stager can observe the stop flag
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=5)

    def __enter__(self) -> "DeviceLoader":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
