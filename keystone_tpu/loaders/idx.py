"""IDX (MNIST ubyte) loader — the distribution format of the real MNIST
corpus (train-images-idx3-ubyte / train-labels-idx1-ubyte, optionally
gzipped).

The reference's MNIST workload reads a CSV conversion
(MnistRandomFFT.scala expects label-first CSV rows); this loader accepts
the UPSTREAM format directly so a staged real corpus works without a
conversion step: staged in either format, the pipeline runs on it.

Format (http-era de facto standard): big-endian header
``[0, 0, dtype_code, ndim] + ndim * int32 dims``, then row-major data.
Only dtype code 0x08 (uint8) is needed for MNIST.
"""

from __future__ import annotations

import gzip
import os
import struct

import numpy as np

from keystone_tpu.loaders.labeled import LabeledData

_DTYPES = {
    0x08: np.uint8, 0x09: np.int8, 0x0B: np.int16,
    0x0C: np.int32, 0x0D: np.float32, 0x0E: np.float64,
}


def _open(path: str):
    if path.endswith(".gz"):
        return gzip.open(path, "rb")
    return open(path, "rb")


def load_idx(path: str) -> np.ndarray:
    """One IDX file → ndarray with the header's shape and dtype.

    Transient read errors (flaky NFS, the ``idx.read`` fault
    site) retry under ``IO_POLICY``; a malformed file (bad magic, short
    payload) is a ValueError that passes straight through — corruption
    is not transient."""
    from keystone_tpu.resilience import faults
    from keystone_tpu.resilience.retry import IO_POLICY

    def _read() -> np.ndarray:
        faults.maybe_raise("idx.read", note=path)
        with _open(path) as f:
            zero, code, ndim = struct.unpack(">HBB", f.read(4))
            if zero != 0 or code not in _DTYPES:
                raise ValueError(
                    f"{path}: not an IDX file (magic {zero:#x}/{code:#x})"
                )
            dims = struct.unpack(f">{ndim}i", f.read(4 * ndim))
            data = np.frombuffer(
                f.read(), dtype=np.dtype(_DTYPES[code]).newbyteorder(">")
            )
        if data.size != int(np.prod(dims)):
            raise ValueError(
                f"{path}: payload {data.size} != header {dims}"
            )
        return data.reshape(dims).astype(_DTYPES[code])

    return IO_POLICY.call(_read, label="idx.read")


def is_idx_path(path: str) -> bool:
    """Heuristic: the conventional ubyte naming, or a valid IDX magic."""
    name = os.path.basename(path)
    if "ubyte" in name or name.endswith(".idx") or name.endswith(".idx.gz"):
        return True
    try:
        with _open(path) as f:
            zero, code, _ = struct.unpack(">HBB", f.read(4))
        return zero == 0 and code in _DTYPES
    except Exception:  # noqa: BLE001 — unreadable/short: not IDX
        return False


def load_labeled_idx(images_path: str, labels_path: str) -> LabeledData:
    """(images idx3, labels idx1) → flattened float rows in [0, 255] +
    int labels, matching the CSV loader's LabeledData contract."""
    imgs = load_idx(images_path)
    labels = load_idx(labels_path)
    if imgs.shape[0] != labels.shape[0]:
        raise ValueError(
            f"image/label count mismatch: {imgs.shape[0]} vs "
            f"{labels.shape[0]}"
        )
    return LabeledData(
        labels=labels.astype(np.int32).reshape(-1),
        data=imgs.reshape(imgs.shape[0], -1).astype(np.float32),
    )


def guess_labels_path(images_path: str) -> str | None:
    """The conventional sibling name: ...images-idx3... → ...labels-idx1...
    Substitutes on the basename only — a directory component containing
    "images" must not be rewritten."""
    head, name = os.path.split(images_path)
    cand = name.replace("images", "labels").replace("idx3", "idx1")
    if cand == name:
        return None
    path = os.path.join(head, cand)
    return path if os.path.exists(path) else None
