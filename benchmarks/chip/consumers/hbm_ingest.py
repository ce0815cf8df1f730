"""Consumer that lands each batch of files in HBM and checksums it there.

The decode, run by the loader's thread, packs the batch's files into one
flat uint8 host buffer, each file starting on a 128-byte boundary, and
copies it and the files' start and end offsets to the device. The
buffer's capacity is the traffic's ``buffer_headroom`` times a batch of
files of the configuration's mean size, rounded up to 8 MiB: the same for
every seed, so every run copies the same bytes and the device program has
one shape. Set-up makes sure that no batch of the epochs a run can reach
exceeds it. The host buffers are
a ring allocated at set-up and reused: a buffer is packed again only
once its last copy to the device has landed, so no batch pays for fresh
pages. The CPU's ``device_put`` may make the host buffer itself the
device array's memory; there each batch gets a fresh buffer. A step is
one jitted per-sample checksum over the buffer, waited for on the host.
The device holds only the stream: the batches in flight, the checksums
and the batches kept for the read-back.

The checksum of a sample of bytes b_0..b_{n-1} is
sum_j mix(b_j) * (K1 * j + K2) mod 2**32, so a changed, moved or missing
byte changes it. After the window, ``check`` compares every consumed
sample's checksum with one computed on the host from the generated files
in the sampler's order, and reads back a sample of whole batches, drawn
from the seed, to compare byte for byte.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from chipbench import gen
from chipbench.runner import Check, log

ALIGN = 128
GRANULE = 8 << 20              # capacity rounds up to this
K1, K2 = 0x9E3779B1, 0x85EBCA77
M1, M2 = 0x7FEB352D, 0x846CA68B
CAPACITY_EPOCHS = 256          # epochs the capacity is checked against


def _mix_np(b: np.ndarray) -> np.ndarray:
    x = (b.astype(np.uint32) + np.uint32(1)) * np.uint32(M1)
    x ^= x >> np.uint32(15)
    return x * np.uint32(M2)


def aligned(n):
    return -(-n // ALIGN) * ALIGN


class Consumer:
    def __init__(self, cell, paths: List[str], files: Dict[str, bytes],
                 tokens, seed: int, devices):
        import jax
        self.jax = jax
        self.device = devices[0]
        self.paths, self.files, self.seed = paths, files, seed
        self.batch = int(cell.traffic["batch"])
        self.warmup_steps = int(cell.traffic["warmup_steps"])
        self.readback = int(cell.traffic["readback_batches"])
        self.sizes = np.array([len(files[p]) for p in paths], np.int64)
        mean_batch = self.batch * float(cell.config["dataset"]["mean_bytes"])
        wanted = float(cell.traffic["buffer_headroom"]) * mean_batch
        self.capacity = -(-int(wanted) // GRANULE) * GRANULE
        n = len(paths)
        per = (n // self.batch) * self.batch
        for epoch in range(CAPACITY_EPOCHS):
            order = gen.epoch_order(n, seed, epoch)
            packed = aligned(self.sizes[order[:per]]).reshape(-1, self.batch)
            largest = int(packed.sum(axis=1).max())
            if largest > self.capacity:
                raise ValueError(f"epoch {epoch} has a batch of {largest} "
                                 f"bytes, over the capacity {self.capacity}")
        # one buffer being packed, one in each staged batch, one in the step
        depth = int(cell.traffic["loader_depth"])
        self._ring = [[np.empty(self.capacity, np.uint8), None]
                      for _ in range(depth + 2)]
        self._turn = 0
        self._fresh = self.device.platform == "cpu"
        self.fn = None
        self.checks: List = []          # device arrays, one per step
        self.kept: Dict[int, tuple] = {}
        self.delivered = 0               # bytes of samples
        self.padding = 0                 # bytes copied that hold no sample
        self._rng = np.random.default_rng((seed, 3))   # read-back sample

    # -- the loader thread -------------------------------------------------
    def decode(self, blobs: List[bytes]):
        n = len(blobs)
        sizes = np.fromiter(map(len, blobs), np.int64, n)
        packed = np.cumsum(aligned(sizes))
        offs = np.empty((2, self.batch), np.int64)   # starts, ends
        offs[:] = packed[-1] if n else 0
        offs[0, :n] = packed - aligned(sizes)
        offs[1, :n] = offs[0, :n] + sizes
        if n and offs[1, n - 1] > self.capacity:
            raise ValueError(f"batch of {offs[1, n - 1]} bytes exceeds the "
                             f"capacity {self.capacity}")
        # the loader has one producer thread, the only caller
        slot = self._ring[self._turn % len(self._ring)]
        self._turn += 1
        if slot[1] is not None:
            slot[1].block_until_ready()     # its last copy has landed
        if self._fresh:
            slot[0] = np.empty(self.capacity, np.uint8)
        buf = memoryview(slot[0])
        for b, s in zip(blobs, offs[0, :n].tolist()):
            buf[s:s + len(b)] = b
        put = self.jax.device_put
        slot[1] = put(slot[0], self.device)
        return (slot[1], put(offs.astype(np.int32), self.device),
                int(sizes.sum()))

    # -- the consumer ------------------------------------------------------
    def _compile(self):
        import jax
        import jax.numpy as jnp
        u32 = jnp.uint32
        nb = self.capacity // ALIGN

        def checksum(buf, offs):
            # blocks [first, last) hold a sample; prefix sums over the
            # blocks' sums give each sample's sums as two differences
            starts, ends = offs[0], offs[1]
            first, last = starts // ALIGN, (ends + ALIGN - 1) // ALIGN
            seg = jnp.cumsum(jnp.zeros((nb,), jnp.int32).at[first].add(
                1, indices_are_sorted=True)) - 1
            blk = jnp.arange(nb, dtype=jnp.int32) * ALIGN
            pos = blk[:, None] + jnp.arange(ALIGN, dtype=jnp.int32)[None, :]
            valid = pos < ends[jnp.clip(seg, 0, ends.shape[0] - 1)][:, None]
            x = (buf.reshape(nb, ALIGN).astype(u32) + u32(1)) * u32(M1)
            x = (x ^ (x >> u32(15))) * u32(M2)
            x = jnp.where(valid, x, u32(0))
            zero = jnp.zeros((1,), u32)
            c0 = jnp.concatenate([zero, jnp.cumsum(x.sum(axis=1))])
            c1 = jnp.concatenate(
                [zero, jnp.cumsum((x * pos.astype(u32)).sum(axis=1))])
            s0 = c0[last] - c0[first]
            local = c1[last] - c1[first] - starts.astype(u32) * s0
            return local * u32(K1) + s0 * u32(K2)

        shapes = (jax.ShapeDtypeStruct((self.capacity,), jnp.uint8),
                  jax.ShapeDtypeStruct((2, self.batch), jnp.int32))
        return jax.jit(checksum).lower(*shapes).compile()

    def setup(self, plane, spans) -> None:
        self.fn = self._compile()
        for _ in range(self.warmup_steps):
            self.step(plane.next(spans))

    def step(self, batch) -> int:
        buf, offs, nbytes = batch
        k = len(self.checks)
        out = self.fn(buf, offs)
        out.block_until_ready()
        self.checks.append(out)
        # a uniform sample of all steps so far (reservoir sampling)
        if len(self.kept) < self.readback:
            self.kept[k] = (buf, offs)
        elif self._rng.integers(k + 1) < self.readback:
            del self.kept[sorted(self.kept)[self._rng.integers(self.readback)]]
            self.kept[k] = (buf, offs)
        self.delivered += nbytes
        self.padding += self.capacity - nbytes
        return self.batch

    # -- after the window --------------------------------------------------
    def reference_checksums(self, wanted: np.ndarray) -> Dict[int, int]:
        """Checksums of the files ``wanted``, from the generated bytes."""
        out = {}
        for i in np.unique(wanted).tolist():
            x = _mix_np(np.frombuffer(self.files[self.paths[i]], np.uint8))
            j = np.arange(x.shape[0], dtype=np.uint32)
            s0 = int(x.sum(dtype=np.uint32))
            s1 = int((x * j).sum(dtype=np.uint32))
            out[i] = (s1 * K1 + s0 * K2) & 0xFFFFFFFF
        return out

    def check(self):
        n_steps = len(self.checks)
        got = np.stack([np.asarray(c) for c in self.checks]).astype(np.int64)
        want_idx = np.stack([
            gen.batch_indices(len(self.paths), self.batch, self.seed, k)
            for k in range(n_steps)])
        ref = self.reference_checksums(want_idx)
        want = np.vectorize(ref.__getitem__, otypes=[np.int64])(want_idx)
        bad_samples = int((got != want).sum())

        bad_bytes = 0
        for k, (buf, offs) in sorted(self.kept.items()):
            host = np.asarray(buf)
            s, e = np.asarray(offs)
            for slot, i in enumerate(want_idx[k].tolist()):
                data = np.frombuffer(self.files[self.paths[i]], np.uint8)
                seen = host[s[slot]:e[slot]]
                if seen.shape != data.shape:
                    bad_bytes += max(seen.size, data.size)
                else:
                    bad_bytes += int((seen != data).sum())
        total = self.delivered + self.padding
        log(f"hbm_ingest: steps={n_steps} capacity={self.capacity} "
              f"delivered_bytes={self.delivered} padding_bytes={self.padding} "
              f"padding_share={self.padding / total if total else 0} "
              f"readback_batches={len(self.kept)}")
        self.checks, self.kept = [], {}
        checks = {"samples_mismatched": Check(bad_samples, 0),
                  "bytes_mismatched_readback": Check(bad_bytes, 0)}
        return checks, n_steps * self.batch, bad_samples
