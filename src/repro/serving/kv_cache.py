"""Paged KV-cache pool + block tables (vLLM-style, TPU-kernel-compatible).

Physical layout matches the paged_attention kernel: per layer a page pool
(n_pages, page_size, kv_heads, head_dim) with per-request block tables.
The pool also carries the metadata the reuse-aware offload policy (§6.2)
consumes: per-page content hashes and observation counts.

The engine can run in two cache modes:
  * "slots"  — contiguous per-slot caches via models.model.init_cache
               (used for CPU integration tests; exact wrt the model)
  * "paged"  — this pool + the Pallas paged kernel (the production mode)

Packed ragged decode (DESIGN.md §10) lives here too: ``RaggedBatch``
describes one step's packed ready set (slot ids + per-slot KV lengths, no
padding).  The step decodes those slots in place on the resident cache
(``models.model.decode_step`` with ``slots``), at a packed width instead of
a dense one padded to ``max_batch``.  ``gather_slot_cache``/
``scatter_slot_cache`` copy the slots' rows out of and back into the
resident tree: around a plain ``decode_step`` they are the reference the
in-place step is tested against.  TensorRT-LLM's ``gpt_attention.md`` argues packed
(non-padded) batching is strictly better; the ragged block-table export
(``ragged_block_tables``) is the paged-kernel shape of the same idea.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np


@dataclass
class PageMeta:
    page_id: int
    #: content hash of the token block this page holds (prefix caching key)
    token_hash: Optional[int] = None
    #: how many times this content has been observed (reuse evidence, §6.2)
    seen_count: int = 0
    request_id: Optional[str] = None
    logical_index: int = -1   # position within the request's table


class PagePool:
    """One layer group's physical page pool + allocation state."""

    def __init__(self, n_pages: int, page_size: int, n_kv_heads: int,
                 head_dim: int, n_layers: int, dtype=jnp.bfloat16):
        self.n_pages = n_pages
        self.page_size = page_size
        self.shape = (n_layers, n_pages, page_size, n_kv_heads, head_dim)
        self.k = jnp.zeros(self.shape, dtype)
        self.v = jnp.zeros(self.shape, dtype)
        self.free: list[int] = list(range(n_pages))
        self.meta: dict[int, PageMeta] = {
            i: PageMeta(page_id=i) for i in range(n_pages)}
        #: content hash -> page id, for prefix reuse
        self.hash_index: dict[int, int] = {}
        self.seen_counts: dict[int, int] = {}

    # -- allocation ---------------------------------------------------------------------

    def pages_needed(self, n_tokens: int) -> int:
        return -(-n_tokens // self.page_size)

    def allocate(self, request_id: str, n_tokens: int,
                 token_blocks: Optional[list[tuple]] = None) -> Optional[list[int]]:
        """Allocate a block table for a request; None if pool exhausted.

        token_blocks: per-page token tuples for content hashing (prefix reuse
        and offload-evidence tracking).
        """
        need = self.pages_needed(n_tokens)
        if len(self.free) < need:
            return None
        table = []
        for i in range(need):
            pid = self.free.pop()
            meta = self.meta[pid]
            meta.request_id = request_id
            meta.logical_index = i
            if token_blocks and i < len(token_blocks):
                h = hash(token_blocks[i])
                meta.token_hash = h
                self.seen_counts[h] = self.seen_counts.get(h, 0) + 1
                meta.seen_count = self.seen_counts[h]
            else:
                # page reused for unhashed content: drop the previous
                # occupant's hash or inventory() would advertise stale content
                meta.token_hash = None
                meta.seen_count = 0
            table.append(pid)
        return table

    def release(self, table: list[int]) -> None:
        for pid in table:
            meta = self.meta[pid]
            meta.request_id = None
            meta.logical_index = -1
            self.free.append(pid)

    def utilization(self) -> float:
        return 1.0 - len(self.free) / self.n_pages

    def inventory(self) -> set[int]:
        """Content hashes resident in allocated pages.

        Exported to the cluster router (§6.2): prefix-affinity routing sends
        a request to the replica whose pool already holds its prompt blocks,
        so the prefix never re-crosses the bridge.
        """
        return {m.token_hash for m in self.meta.values()
                if m.token_hash is not None and m.request_id is not None}

    # -- tensor ops -----------------------------------------------------------------------

    def write_token(self, layer: int, page_id: int, offset: int,
                    k_tok: jax.Array, v_tok: jax.Array) -> None:
        """Write one token's K/V into a page (decode append)."""
        self.k = self.k.at[layer, page_id, offset].set(k_tok.astype(self.k.dtype))
        self.v = self.v.at[layer, page_id, offset].set(v_tok.astype(self.v.dtype))

    def layer_views(self, layer: int) -> tuple[jax.Array, jax.Array]:
        return self.k[layer], self.v[layer]


def block_table_array(tables: dict[str, list[int]], order: list[str],
                      pages_max: int) -> np.ndarray:
    """Dense (B, pages_max) int32 block-table batch for the kernel."""
    out = np.zeros((len(order), pages_max), np.int32)
    for i, rid in enumerate(order):
        t = tables[rid][:pages_max]
        out[i, :len(t)] = t
    return out


def ragged_block_tables(tables: dict[str, list[int]],
                        order: list[str]) -> tuple[np.ndarray, np.ndarray]:
    """Packed (non-padded) block-table batch: one flat int32 page-id vector
    plus (B+1,) int32 row offsets (CSR-style), the paged-kernel shape of a
    ragged batch.  Total size is the pages actually allocated — a dense
    table pads every row to the widest request and ships the padding across
    the bridge with it."""
    flat: list[int] = []
    offsets = np.zeros(len(order) + 1, np.int32)
    for i, rid in enumerate(order):
        t = tables[rid]
        flat.extend(t)
        offsets[i + 1] = offsets[i] + len(t)
    return np.asarray(flat, np.int32), offsets


# ---------------------------------------------------------------------------------
# Packed ragged decode (DESIGN.md §10)
# ---------------------------------------------------------------------------------


@dataclass(frozen=True)
class RaggedBatch:
    """One packed decode step's ready set: slot ids + per-slot KV lengths.

    No padding anywhere: ``size`` is exactly the number of rows the forward
    executes, ``kv_lens`` are the per-slot prefix depths the pricing reads
    (``ComputeModel.decode_charge_packed``), and ``total_kv_tokens`` is the
    step's KV read traffic in tokens.  Slots keep engine order (ascending),
    so row ``i`` of the packed batch is slot ``slots[i]`` — the scatter back
    into the resident cache and the per-row token drain both key on that.
    """

    slots: tuple[int, ...]
    kv_lens: tuple[int, ...]

    def __post_init__(self):
        if len(self.slots) != len(self.kv_lens):
            raise ValueError(
                f"ragged batch needs one kv_len per slot: "
                f"{len(self.slots)} slots vs {len(self.kv_lens)} lens")

    @classmethod
    def from_slots(cls, pairs: Sequence[tuple[int, int]]) -> "RaggedBatch":
        """Build from (slot, kv_len) pairs, preserving caller order."""
        return cls(slots=tuple(int(s) for s, _ in pairs),
                   kv_lens=tuple(int(k) for _, k in pairs))

    @property
    def size(self) -> int:
        return len(self.slots)

    @property
    def total_kv_tokens(self) -> int:
        return int(sum(self.kv_lens))

    def offsets(self) -> np.ndarray:
        """CSR-style (size+1,) cumulative KV offsets of the packed rows."""
        out = np.zeros(self.size + 1, np.int64)
        np.cumsum(np.asarray(self.kv_lens, np.int64), out=out[1:])
        return out

    def slot_array(self) -> np.ndarray:
        return np.asarray(self.slots, np.int32)


def _walk_cache(tree, fn, *rest):
    """Apply ``fn`` to every leaf of a slot cache tree (dict/list nesting —
    the same structure ``models.model.init_cache`` builds)."""
    if isinstance(tree, dict):
        return {k: _walk_cache(tree[k], fn, *(r[k] for r in rest))
                for k in tree}
    if isinstance(tree, list):
        return [_walk_cache(t, fn, *r) for t, *r in zip(tree, *rest)]
    return fn(tree, *rest)


def gather_slot_cache(caches: dict, slots, *, scan_layers: bool) -> dict:
    """Packed view of ``slots``' rows from the full resident cache tree.

    The slot axis is 0 for per-layer leaves and 1 for scan-stacked
    ``blocks`` leaves — the same rule the engine's prefill insertion uses,
    so the two stay structurally consistent by construction.
    """
    slots = jnp.asarray(slots, jnp.int32)
    out = {}
    for key, sub in caches.items():
        stacked = scan_layers and key == "blocks"
        take = ((lambda a: a[:, slots]) if stacked
                else (lambda a: a[slots]))
        out[key] = _walk_cache(sub, take)
    return out


def scatter_slot_cache(caches: dict, packed: dict, slots, *,
                       scan_layers: bool) -> dict:
    """Write a packed cache tree's rows back into the resident tree at
    ``slots``.  Duplicate slot ids are allowed only when their rows carry
    identical values (the bucket-padding case: pad rows duplicate a real
    slot and recompute the identical update)."""
    slots = jnp.asarray(slots, jnp.int32)
    out = {}
    for key, sub in caches.items():
        stacked = scan_layers and key == "blocks"
        put = ((lambda full, one: full.at[:, slots].set(
                    one.astype(full.dtype))) if stacked
               else (lambda full, one: full.at[slots].set(
                    one.astype(full.dtype))))
        out[key] = _walk_cache(sub, put, packed[key])
    return out
