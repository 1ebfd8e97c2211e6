"""Block-allocated paged KV cache for the continuous-batching serve runtime.

The dense decode cache (``models.layers.init_kv_cache``) reserves
``batch x max_seq`` rows up front — a request generating 8 tokens from a
12-token prompt holds the same HBM as one filling the whole window, and a
fixed batch can never be backfilled mid-flight.  This module replaces it
with the paged layout production servers use (vLLM's PagedAttention):

  * the cache is a pool of fixed-size **blocks** —
    ``(L, num_blocks, block_size, Hkv·hd)`` per K and V, a position's heads
    side by side in one row, so that the two minor dimensions fill whole
    (sublane, lane) tiles on the TPU for any head size (a ``(…, Hkv, hd)``
    block pads heads of 64 to 128 lanes) — allocated to requests in
    ``block_size``-token units by a host-side free list
    (``BlockAllocator``);
  * each request owns a **block table** (its ordered block ids); logical
    position ``p`` of a request lives at ``(table[p // bs], p % bs)``;
  * block 0 is a reserved **scratch block**: pad rows of a bucketed batch
    point their whole table at it, so their writes never touch a live
    request's cache and their reads are causally masked anyway.

The device side stays pure array math: ``write_prefill_blocks`` scatters a
prefill's per-layer K/V into the pool through a block table, and
``models.layers.attention_decode_paged`` gathers a slot's table back into a
dense per-slot view for the masked decode attention.  Admission, eviction
and the free list live on the host (``launch.scheduler``) — allocator state
never rides a traced value, so the decode step keeps its fixed shape.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Reserved scratch block: pad rows of a bucketed batch write (and point
# their table entries) here.  Never allocated, never read unmasked.
SCRATCH_BLOCK = 0


class BlockAllocator:
    """Host-side free-list allocator over the pool's block ids.

    ``alloc`` returns None (instead of raising) when the pool can't satisfy
    the request — the scheduler's signal to keep the request queued until
    evictions return blocks.  Double-frees and frees of never-allocated ids
    raise: a block table pointing at a re-issued block is silent cache
    corruption, the one failure mode a paged cache must never hide.

    ``fault_hook`` is the fault-injection seam (``runtime.fault_injection``):
    when set, it is consulted on every ``alloc`` and a True return fails the
    allocation even though the pool could satisfy it — so injected transient
    allocation failures flow through the exact code path organic pool
    exhaustion takes (the caller queues or preempts, never crashes).
    """

    def __init__(self, num_blocks: int, reserved: int = 1):
        self.fault_hook = None  # Callable[[int], bool] | None
        if num_blocks <= reserved:
            raise ValueError(
                f"pool of {num_blocks} blocks leaves nothing to allocate "
                f"after {reserved} reserved scratch block(s)"
            )
        self.num_blocks = num_blocks
        self.reserved = reserved
        # descending so pop() hands out low ids first (determinism only —
        # block ids never affect numerics, gathers go through the table)
        self._free = list(range(num_blocks - 1, reserved - 1, -1))
        self._live: set[int] = set()

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def live_blocks(self) -> int:
        return len(self._live)

    def alloc(self, n: int) -> list[int] | None:
        """Allocate ``n`` blocks, or None when fewer than ``n`` are free
        (or an injected fault fails the attempt — see ``fault_hook``)."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if self.fault_hook is not None and self.fault_hook(n):
            return None
        if n > len(self._free):
            return None
        blocks = [self._free.pop() for _ in range(n)]
        self._live.update(blocks)
        return blocks

    def free(self, blocks: list[int]) -> None:
        for b in blocks:
            if b not in self._live:
                raise ValueError(
                    f"free of block {b} which is not live (double-free or "
                    "never allocated)"
                )
            self._live.discard(b)
            self._free.append(b)


class PagedKVCache:
    """The device pools + the host allocator, sized for one serving run.

    ``k`` / ``v`` are ``(L, num_blocks, block_size, Hkv·hd)`` bf16 — the
    serving dtype of the dense cache, block-paged, heads flattened into the
    minor dimension.  The pools are plain arrays the caller threads through
    the jitted prefill/decode steps (donated, so updates are in-place); this
    object only tracks allocator state between steps.  ``device_bytes`` is
    what K and V take on the device, padding included; ``positions`` is
    ``num_blocks × block_size``.
    """

    def __init__(self, cfg, num_blocks: int, block_size: int,
                 layers: int | None = None):
        L = layers if layers is not None else cfg.num_layers
        shape = (L, num_blocks, block_size, cfg.kv_dim)
        self.k = jnp.zeros(shape, jnp.bfloat16)
        self.v = jnp.zeros(shape, jnp.bfloat16)
        self.block_size = block_size
        self.num_blocks = num_blocks
        self.positions = num_blocks * block_size
        self.allocator = BlockAllocator(num_blocks, reserved=1)

    @property
    def device_bytes(self) -> int:
        return self.k.on_device_size_in_bytes() + self.v.on_device_size_in_bytes()

    def blocks_for(self, tokens: int) -> int:
        """Blocks needed to cache ``tokens`` positions."""
        return -(-tokens // self.block_size)

    def alloc(self, tokens: int) -> list[int] | None:
        """Allocate a request's blocks for ``tokens`` cache positions, or
        None when the pool is exhausted (caller queues the request)."""
        return self.allocator.alloc(self.blocks_for(tokens))

    def free(self, blocks: list[int]) -> None:
        self.allocator.free(blocks)


def write_prefill_blocks(pool_k, pool_v, k_all, v_all, table):
    """Scatter a prefill's per-layer K/V into the block pools.

    ``k_all`` / ``v_all``: (L, B, S, Hkv·hd), the pools' row layout, with S
    a multiple of the block size; ``table``: (B, S // bs) int32 block ids
    per row.  Table entries beyond a request's allocation point at the
    scratch block — their (pad-position) K/V lands there and is never read
    unmasked.  Returns the updated pools (pure; callers jit with donation).
    """
    L, B, S, C = k_all.shape
    bs = pool_k.shape[2]
    nb = S // bs
    with jax.named_scope("kv.scatter"):
        k_r = k_all.reshape(L, B, nb, bs, C).astype(pool_k.dtype)
        v_r = v_all.reshape(L, B, nb, bs, C).astype(pool_v.dtype)
        return pool_k.at[:, table].set(k_r), pool_v.at[:, table].set(v_r)
