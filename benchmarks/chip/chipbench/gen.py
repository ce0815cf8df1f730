"""Seeded inputs: the dataset a configuration describes, and its order.

Everything here is made from ``--seed`` on the host and is the plain
reference for what the data plane must deliver: the files' bytes, and
the global uniform shuffle that orders them into batches.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np


# the one stream the set of file sizes is drawn from, whatever the seed
SIZE_SET_STREAM = (0, 0)


def file_sizes(ds: Dict, seed: int) -> np.ndarray:
    """Lognormal file sizes with the configuration's mean and sigma,
    clipped to [min_bytes, max_bytes]. The set of sizes depends on the
    configuration alone and the seed only deals it out to the files, so
    every seed stores, and a run reads, the same bytes in all."""
    sigma = float(ds["sigma"])
    mu = np.log(float(ds["mean_bytes"])) - sigma * sigma / 2
    sizes = np.random.default_rng(SIZE_SET_STREAM).lognormal(
        mu, sigma, int(ds["num_files"]))
    sizes = np.clip(np.rint(sizes), ds["min_bytes"], ds["max_bytes"]).astype(
        np.int64)
    return np.random.default_rng((seed, 0)).permutation(sizes)


def small_files(ds: Dict, seed: int) -> Tuple[List[str], Dict[str, bytes]]:
    """ImageNet-shaped files of random (incompressible) bytes in class
    directories. Returns (paths in dataset-index order, {path: data}),
    each file's data a view of one buffer."""
    sizes = file_sizes(ds, seed)
    classes = int(ds["num_classes"])
    total = int(sizes.sum())
    words = np.random.Generator(np.random.SFC64((seed, 1))).integers(
        0, 2 ** 64 - 1, -(-total // 8), dtype=np.uint64, endpoint=True)
    blob = memoryview(words.view(np.uint8)[:total])
    ends = np.cumsum(sizes)
    paths, files = [], {}
    for i, (end, size) in enumerate(zip(ends.tolist(), sizes.tolist())):
        cls = i % classes
        path = f"train/n{cls:08d}/n{cls:08d}_{i:07d}.JPEG"
        paths.append(path)
        files[path] = blob[end - size:end]     # a view: no copy
    return paths, files


def shifted_cdf(trans: np.ndarray) -> np.ndarray:
    """Row s of the transition table's CDF, shifted up by s, all rows in
    one sorted array."""
    k = trans.shape[0]
    return (np.arange(k)[:, None] + np.cumsum(trans, axis=1)).ravel()


def next_states(flat: np.ndarray, k: int, state: np.ndarray,
                u: np.ndarray) -> np.ndarray:
    """The first state whose CDF entry exceeds ``u``, in each state's row:
    the count of that row's entries <= u, found in one sorted lookup."""
    nxt = np.searchsorted(flat, state + u, side="right") - state * k
    return np.minimum(nxt, k - 1)


def markov_tokens(num_samples: int, seq_len: int, vocab: int, seed: int
                  ) -> np.ndarray:
    """(num_samples, seq_len) int32 token ids from an order-1 Markov chain
    over min(vocab, 64) states, so a model has structure to learn."""
    rng = np.random.default_rng((seed, 2))
    k = min(vocab, 64)
    trans = rng.dirichlet(np.ones(k) * 0.2, size=k)
    flat = shifted_cdf(trans)
    out = np.empty((num_samples, seq_len), dtype=np.int32)
    state = rng.integers(0, k, num_samples)
    for t in range(seq_len):
        out[:, t] = state
        state = next_states(flat, k, state, rng.random(num_samples))
    # spread the k states over the whole vocabulary
    ids = rng.choice(vocab, k, replace=False).astype(np.int32)
    return ids[out]


def token_files(ds: Dict, vocab: int, seed: int
                ) -> Tuple[List[str], Dict[str, bytes], np.ndarray]:
    """Token records, one little-endian int32 file per sequence."""
    tokens = markov_tokens(int(ds["num_files"]), int(ds["seq_len"]), vocab,
                           seed)
    paths = [f"lm/seq_{i:07d}.bin" for i in range(tokens.shape[0])]
    files = {p: tokens[i].astype("<i4").tobytes()
             for i, p in enumerate(paths)}
    return paths, files, tokens


def make_dataset(ds: Dict, seed: int, vocab: int = 0):
    """(paths, files, tokens or None) for the configuration's dataset."""
    if ds["kind"] == "small_files":
        paths, files = small_files(ds, seed)
        return paths, files, None
    if ds["kind"] == "token_records":
        return token_files(ds, vocab, seed)
    raise ValueError(f"unknown dataset kind {ds['kind']!r}")


def epoch_order(num_samples: int, seed: int, epoch: int) -> np.ndarray:
    """The global uniform shuffle of one epoch: every index once."""
    return np.random.default_rng((seed, epoch)).permutation(num_samples)


def batch_indices(num_samples: int, batch: int, seed: int, step: int
                  ) -> np.ndarray:
    """Dataset indices of global step ``step`` (epochs of whole batches)."""
    per_epoch = num_samples // batch
    epoch, k = divmod(step, per_epoch)
    return epoch_order(num_samples, seed, epoch)[k * batch:(k + 1) * batch]
