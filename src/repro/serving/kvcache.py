"""Paged KV cache: fixed-size blocks, a free-list allocator, per-request
block tables, and placement-aware residency.

Layout
------
The cache for every attention layer is a *pool* whose leading axes are
``(num_blocks, block_size)`` instead of ``(batch, max_len)`` — i.e. the
pools pytree is exactly ``models.init_cache(cfg, batch=num_blocks,
max_len=block_size)``. A request's logical KV sequence is the
concatenation of the fixed-size blocks its *block table* names, so
persistent cache memory grows with the tokens actually cached (rounded
up to ``block_size``), not with ``max_batch * max_len`` as the old
slot engine preallocated.

Block 0 is reserved as the *null block*: unallocated table entries and
padded batch rows point at it, so gathers of short tables read zeros
(masked off by causal attention) and scatters from inactive rows land
harmlessly in scratch.

The per-step decode path is pure and traceable (so a
:class:`~repro.api.PartitionPlan` can own it):

    dense   = gather_pages(pools, block_tables)      # (B, W*bs, ...)
    logits, new_dense = decode_step(cfg, params, dense, tokens, lengths)
    pools   = scatter_token(pools, new_dense, block_tables, lengths)

``gather_pages`` materializes a *transient* contiguous view per step
(the XLA analogue of a paged-attention kernel's in-kernel indirection);
the persistent footprint is the pool. ``scatter_token`` writes back only
the one token each row appended, into the block its table maps that
position to.

Admission writes a prefilled prompt into its blocks with
:func:`write_prompt`: one jitted scatter per request per device holding
pool leaves, with the pools donated, so the blocks are written in place
and no pool leaf is copied.

Placement-aware residency
-------------------------
With a partition plan, each pool leaf is allocated on the device the
plan assigns that leaf's *consuming ops* to (the ops of the layer whose
attention reads it) — resolved through the traced program's input
nodes (:func:`resolve_pool_devices`). Tensor residency follows the
partition, not the other way around.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

#: block id every unallocated table entry (and padded row) points at
NULL_BLOCK = 0


class OutOfBlocks(RuntimeError):
    """The free list is empty — caller must evict or wait."""


class BlockAllocator:
    """Free-list allocator over ``num_blocks`` fixed-size blocks.

    Block ids ``[0, reserved)`` are never handed out (block 0 is the
    null block). Allocation is LIFO over the free list; the invariants
    — no double allocation, no foreign/double free, conservation of
    ``num_free + num_allocated`` — are checked on every operation and
    by :meth:`check`.
    """

    def __init__(self, num_blocks: int, reserved: int = 1):
        if num_blocks <= reserved:
            raise ValueError(
                f"need more than {reserved} blocks (got {num_blocks})")
        self.num_blocks = int(num_blocks)
        self.reserved = int(reserved)
        self._free: list[int] = list(range(num_blocks - 1,
                                           self.reserved - 1, -1))
        self._allocated: set[int] = set()
        self.peak_in_use = 0

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_in_use(self) -> int:
        return len(self._allocated)

    @property
    def capacity(self) -> int:
        """Allocatable blocks (total minus reserved)."""
        return self.num_blocks - self.reserved

    def alloc(self) -> int:
        if not self._free:
            raise OutOfBlocks(
                f"all {self.capacity} KV blocks in use — evict a request "
                f"or raise num_blocks")
        b = self._free.pop()
        self._allocated.add(b)
        self.peak_in_use = max(self.peak_in_use, self.num_in_use)
        return b

    def alloc_many(self, n: int) -> list[int]:
        if n > self.num_free:
            raise OutOfBlocks(
                f"need {n} KV blocks, only {self.num_free} free")
        return [self.alloc() for _ in range(n)]

    def free(self, block: int) -> None:
        if block not in self._allocated:
            raise ValueError(
                f"block {block} is not allocated (double free or foreign "
                f"block)")
        self._allocated.remove(block)
        self._free.append(block)

    def free_many(self, blocks: list[int]) -> None:
        for b in blocks:
            self.free(b)

    def check(self) -> None:
        """Assert the allocator invariants (cheap; used by tests and the
        engine's drain check)."""
        free = set(self._free)
        assert len(free) == len(self._free), "free list has duplicates"
        assert not (free & self._allocated), \
            "block both free and allocated"
        assert free | self._allocated == set(
            range(self.reserved, self.num_blocks)), "blocks lost"


# ---------------------------------------------------------------------------
# pool pytree helpers
# ---------------------------------------------------------------------------
def supported_reason(cfg) -> str | None:
    """None when ``cfg`` can serve through the paged cache, else why not.

    Paging needs every cache leaf to carry a sequence axis (attention
    K/V, MLA latents). Recurrent kinds (mamba/rwkv) keep O(1) state with
    no sequence axis to page; encoder-only archs have no decode step;
    non-token frontends have no prompt tokens to prefill.
    """
    if cfg.encoder_only:
        return "encoder-only arch has no decode step"
    if cfg.frontend is not None:
        return "non-token frontend has no token prompts to serve"
    if not cfg.causal:
        return "non-causal attention cannot decode autoregressively"
    kinds = tuple(cfg.prelude) + tuple(cfg.block_pattern)
    bad = sorted({k for k in kinds
                  if k == "rwkv" or k.startswith("mamba")})
    if bad:
        return (f"recurrent layer kinds {bad} keep O(1) state with no "
                f"sequence axis to page")
    return None


def init_pools(cfg, num_blocks: int, block_size: int):
    """The paged pools pytree: ``init_cache`` with the batch axis
    reinterpreted as blocks and the sequence axis as the within-block
    offset."""
    from repro.models import init_cache
    reason = supported_reason(cfg)
    if reason is not None:
        raise NotImplementedError(
            f"{cfg.name}: paged serving unsupported — {reason}")
    return init_cache(cfg, num_blocks, block_size)


def _bdim(path) -> int:
    """Block axis of a pool leaf (batch axis of the dense view): leaves
    under ``periods`` are stacked with a leading num_periods axis."""
    keys = [getattr(p, "key", None) for p in path]
    return 1 if "periods" in keys else 0


def gather_pages(pools, block_tables: jax.Array):
    """Pools → dense per-request caches via the block tables.

    ``block_tables``: (B, W) int32, entries are block ids (NULL_BLOCK
    where unallocated). Each leaf ``(..., nb, bs, *t)`` becomes
    ``(..., B, W*bs, *t)`` — the contiguous layout ``decode_step``
    expects, with ``max_len = W * block_size``.
    """
    def one(path, pool):
        b = _bdim(path)
        dense = jnp.take(pool, block_tables, axis=b)
        shape = dense.shape
        return dense.reshape(shape[:b] + (shape[b],
                                          shape[b + 1] * shape[b + 2])
                             + shape[b + 3:])
    return jax.tree_util.tree_map_with_path(one, pools)


def scatter_token(pools, new_dense, block_tables: jax.Array,
                  lengths: jax.Array):
    """Write back the one token each row appended at position
    ``lengths[r]`` of its dense view, into block
    ``block_tables[r, lengths[r] // bs]`` at offset ``lengths[r] % bs``.

    Rows whose table maps the position to the null block (padding /
    inactive rows) scatter into scratch; duplicate null destinations are
    harmless because nothing ever reads unmasked null content.
    """
    def one(path, pool, dense):
        b = _bdim(path)
        bs = pool.shape[b + 1]
        nb = pool.shape[b]
        blk = block_tables[jnp.arange(block_tables.shape[0]),
                           lengths // bs]                     # (B,)
        dest = blk * bs + lengths % bs                        # (B,)
        tok = jnp.take_along_axis(
            dense, lengths.reshape((1,) * b + (-1, 1)
                                   + (1,) * (dense.ndim - b - 2)),
            axis=b + 1)                                       # (...,B,1,*t)
        tok = jnp.squeeze(tok, axis=b + 1)                    # (...,B,*t)
        flat = pool.reshape(pool.shape[:b] + (nb * bs,) + pool.shape[b + 2:])
        if b == 0:
            flat = flat.at[dest].set(tok.astype(flat.dtype))
        else:
            flat = flat.at[:, dest].set(tok.astype(flat.dtype))
        return flat.reshape(pool.shape)
    return jax.tree_util.tree_map_with_path(one, pools, new_dense)


def write_prompt(pools, blocks: list[int], dense_caches, row: int,
                 plen: int, block_size: int):
    """Copy one prefilled request's cache rows ``[0, plen)`` from the
    dense prefill caches (row ``row``) into its allocated ``blocks``.

    Runs once per admission, outside the jitted step, as one jitted
    scatter per pool device (:func:`device_groups`): the pools are
    donated, so XLA writes the request's blocks in place instead of
    copying each leaf. The request's dense row is committed to each
    group's device first, so placement-aware pools never see
    cross-device ops. Positions at or past ``plen`` in the last block
    keep their contents, and blocks past ``ceil(plen / block_size)``
    are left alone.

    The compiled scatter depends only on the shapes of the pool leaves
    and of the dense row (its padded length ``S``): ``blocks`` is
    padded to ``ceil(S / block_size)`` ids, and ``plen`` is traced.
    """
    leaves, treedef = jax.tree_util.tree_flatten_with_path(pools)
    dense = jax.tree_util.tree_leaves(dense_caches)
    bdims = [_bdim(path) for path, _ in leaves]
    out = [leaf for _, leaf in leaves]
    rows = _take_row(tuple(dense), np.int32(row), tuple(bdims))
    b0 = bdims[0]
    nb, S = out[0].shape[b0], rows[0].shape[b0]
    if plen > S:
        raise ValueError(f"plen {plen} exceeds the prefill length {S}")
    ids = np.full((-(-S // block_size),), nb, np.int32)  # nb: dropped
    n = min(len(blocks), -(-plen // block_size))
    ids[:n] = blocks[:n]
    for dev, idx in device_groups(out).items():
        grows = tuple(rows[i] if dev is None or _leaf_device(rows[i]) == dev
                      else jax.device_put(rows[i], dev) for i in idx)
        new = _scatter_rows(tuple(out[i] for i in idx), grows, ids,
                            np.int32(plen), tuple(bdims[i] for i in idx))
        for i, leaf in zip(idx, new):
            out[i] = leaf
    return jax.tree_util.tree_unflatten(treedef, out)


@functools.partial(jax.jit, static_argnums=(2,))
def _take_row(dense, row, bdims):
    """Row ``row`` of each dense leaf: ``(..., B, S, *t)`` ->
    ``(..., S, *t)``."""
    return tuple(jax.lax.dynamic_index_in_dim(d, row, b, keepdims=False)
                 for d, b in zip(dense, bdims))


@functools.partial(jax.jit, static_argnums=(4,), donate_argnums=(0,))
def _scatter_rows(pools, rows, ids, plen, bdims):
    """``write_prompt``'s device half: scatter each dense row
    ``(..., S, *t)`` into its pool leaf ``(..., nb, bs, *t)`` at the
    block ids ``ids`` (``ceil(S / bs)`` of them, ``nb`` where there is
    no block to write), keeping positions ``>= plen``."""
    out = []
    for pool, drow, b in zip(pools, rows, bdims):
        bs = pool.shape[b + 1]
        nw = ids.shape[0]
        pad = [(0, 0)] * drow.ndim
        pad[b] = (0, nw * bs - drow.shape[b])
        new = jnp.pad(drow, pad).reshape(
            drow.shape[:b] + (nw, bs) + drow.shape[b + 1:]
        ).astype(pool.dtype)                              # (...,nw,bs,*t)
        old = jnp.take(pool, ids, axis=b, mode="clip")
        pos = jnp.arange(nw * bs, dtype=jnp.int32).reshape(nw, bs)
        keep = (pos < plen).reshape((1,) * b + (nw, bs)
                                    + (1,) * (drow.ndim - b - 1))
        sl = (slice(None),) * b + (ids,)
        out.append(pool.at[sl].set(jnp.where(keep, new, old), mode="drop"))
    return tuple(out)


def device_groups(leaves) -> dict:
    """Leaf indices grouped by the one device each leaf lives on (key
    ``None`` for a leaf on several devices or on none), in leaf order:
    the calls :func:`write_prompt` makes."""
    groups: dict = {}
    for i, leaf in enumerate(leaves):
        groups.setdefault(_leaf_device(leaf), []).append(i)
    return groups


def _leaf_device(leaf):
    try:
        devs = leaf.devices()
        return next(iter(devs)) if len(devs) == 1 else None
    except (AttributeError, TypeError):
        return None


# ---------------------------------------------------------------------------
# placement-aware residency
# ---------------------------------------------------------------------------
def resolve_pool_devices(plan, n_params_leaves: int, pools,
                         devices: list) -> list:
    """Device for every pool leaf under ``plan``: the device the plan
    assigns the leaf's graph *input node* to (which Step-2 co-locates
    with the attention ops consuming it — the placement-residency rule).

    The traced decode function's flat inputs are
    ``(params..., pools..., block_tables, tokens, lengths)``, so pool
    leaf ``i`` is input node ``input_nodes[n_params_leaves + i]``.
    """
    prog = plan.traced.program
    leaves = jax.tree_util.tree_leaves(pools)
    out = []
    for i in range(len(leaves)):
        nid = prog.input_nodes[n_params_leaves + i]
        out.append(devices[int(plan.assignment[nid])])
    return out


def place_pools(plan, n_params_leaves: int, pools, devices: list):
    """``device_put`` every pool leaf onto its plan-resolved device.
    Returns (placed_pools, leaf_devices)."""
    devs = resolve_pool_devices(plan, n_params_leaves, pools, devices)
    leaves, treedef = jax.tree_util.tree_flatten(pools)
    placed = [jax.device_put(leaf, d) for leaf, d in zip(leaves, devs)]
    return jax.tree_util.tree_unflatten(treedef, placed), devs


__all__ = [
    "NULL_BLOCK", "OutOfBlocks", "BlockAllocator", "supported_reason",
    "init_pools", "gather_pages", "scatter_token", "write_prompt",
    "device_groups", "resolve_pool_devices", "place_pools",
]
