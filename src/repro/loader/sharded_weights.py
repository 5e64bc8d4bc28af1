"""Sharded weight files: a minimal safetensors-like container.

Layout: <dir>/shard_<i>.bin + index.json mapping tensor name -> (shard,
offset, shape, dtype).  Supports zero-copy (mmap-style) reads — the
"fastsafetensors" path — and per-tensor deserialize reads (the baseline
path the paper measures at 287 s for GPT-OSS-120B).
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import Iterator

import ml_dtypes  # noqa: F401  (registers bfloat16 etc. with numpy)
import numpy as np


def save_sharded(out_dir: str, tensors: dict, *, n_shards: int = 4) -> None:
    os.makedirs(out_dir, exist_ok=True)
    names = list(tensors)
    index = {"shards": n_shards, "tensors": {}}
    buffers = [bytearray() for _ in range(n_shards)]
    for i, name in enumerate(names):
        arr = np.asarray(tensors[name])
        shard = i % n_shards
        index["tensors"][name] = {
            "shard": shard, "offset": len(buffers[shard]),
            "shape": list(arr.shape), "dtype": str(arr.dtype),
        }
        buffers[shard].extend(arr.tobytes())
    for s, buf in enumerate(buffers):
        with open(os.path.join(out_dir, f"shard_{s}.bin"), "wb") as f:
            f.write(bytes(buf))
    with open(os.path.join(out_dir, "index.json"), "w") as f:
        json.dump(index, f)


@dataclass
class ShardedCheckpoint:
    path: str

    def __post_init__(self):
        with open(os.path.join(self.path, "index.json")) as f:
            self.index = json.load(f)

    @property
    def n_shards(self) -> int:
        return self.index["shards"]

    def shard_tensors(self, shard: int) -> list[str]:
        return [n for n, m in self.index["tensors"].items() if m["shard"] == shard]

    def shard_bytes(self, shard: int) -> int:
        return os.path.getsize(os.path.join(self.path, f"shard_{shard}.bin"))

    def total_bytes(self) -> int:
        return sum(self.shard_bytes(s) for s in range(self.n_shards))

    def read_tensor(self, name: str) -> np.ndarray:
        meta = self.index["tensors"][name]
        dtype = np.dtype(meta["dtype"])
        count = int(np.prod(meta["shape"])) if meta["shape"] else 1
        with open(os.path.join(self.path, f"shard_{meta['shard']}.bin"), "rb") as f:
            f.seek(meta["offset"])
            buf = f.read(count * dtype.itemsize)
        return np.frombuffer(buf, dtype=dtype).reshape(meta["shape"])

    def iter_shard(self, shard: int) -> Iterator[tuple[str, np.ndarray]]:
        for name in self.shard_tensors(shard):
            yield name, self.read_tensor(name)
