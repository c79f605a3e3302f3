"""The serve engine's sequence state: a paged KV cache for the layers that
attend, and a slot-indexed recurrent-state store for the recurrent layers.

TWO KINDS of per-sequence state live side by side. A layer that ATTENDS
keeps a key and a value per token: pages of a preallocated pool, below. A
layer of a RECURRENT kind (Gated DeltaNet, ``"linear"``; Mamba-1,
``"mamba"``) keeps a fixed-size state per sequence however long the
sequence is — a [heads, d_k, d_v] float32 array (a matrix a head for the
delta rule; for Mamba one "head" of [d_state, channels], the channels on the
lanes) and the last ``taps − 1`` inputs of its convolution — in
``StateStore``, indexed by the
engine's batch SLOT, one more slot than the engine has (the trash slot).
Pages are allocated at admission and freed at completion; a slot's state
needs no allocator (the slot is the allocation) and is reset inside the
first prefill chunk of whoever takes the slot next. A model's layers index
each store by their place among the layers of their kind
(``TransformerConfig.kind_index``): the pool spans the attending layers
only, the state store the recurrent ones.

The vLLM (SOSP '23) memory model in jax_graft form: decode K/V state
lives in PAGES of ``page_size`` token slots, preallocated as one device
pool per layer side — shape [attending layers, num_pages + 1, n_kv_heads,
page_size, head_dim] (head-major inside a page: a (page, kv-head) slab
[page_size, head_dim] is what the TPU compiler can tile, and a page's
heads lie together, so the paged kernel's block is one page of several
kv-heads in one contiguous piece). Inside a compiled program the pool
keeps that one layout from parameter to result: ``write_rows`` stores a token's
K/V rows with a scatter whose only window is ``head_dim`` (in place, no
relayout), and the kernel takes the WHOLE pool with the layer in its
BlockSpec index map (``ops.flash_attention_decode(..., layer=l)``), so no
layer is sliced out for it. The scatter this replaced,
``pool.at[l, pid, :, row].set(k)``, has a (head, head_dim) window, which
XLA serves only from a row-major-pages layout: it relaid the whole pool
at every program's entry and exit.

A sequence owns an ordered page table (host-side int32 row); growing by
one token touches exactly one page row, and
completion returns the pages to a free list with NO copying — the next
sequence overwrites them in place (pages carry no ownership state on
device; the page table is the only source of truth).

Page index ``num_pages`` (the +1) is the TRASH page: masked writes from
inactive batch slots and prefill padding are steered there instead of
predicating the scatter — its contents are never read (no page table
ever names it inside a live prefix).

The allocator is deliberately host-side and trivial: a LIFO free list.
LIFO maximizes page reuse locality (a just-freed page is hot in whatever
cache hierarchy applies) and makes the leak check exact —
``free_count`` must return to ``num_pages`` when the engine drains,
which the engine tests and the serve worker assert.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional


class PoolExhausted(Exception):
    """Raised when an allocation cannot be satisfied — admission control
    must catch this and hold the request, never the decode step."""


def pages_needed(tokens: int, page_size: int) -> int:
    """Pages required to hold ``tokens`` K/V positions (ceil)."""
    return max(1, -(-int(tokens) // int(page_size)))


@dataclass(frozen=True)
class StateStore:
    """Geometry of the recurrent-state store: for each of ``n_layers``
    recurrent layers and each of ``slots`` batch slots (+ 1: the trash slot)
    the recurrence's state ``[heads, d_k, d_v]`` and the convolution's tail
    ``[conv_rows, conv_channels]`` (the last taps − 1 inputs), float32. The
    two device arrays are the engine's (donated to its programs and handed
    back, like the pools); a decode step updates the state in place through
    the kind's step (``ops.gated_delta_step`` / ``ops.selective_scan_step``
    with ``layer=, slots=``), a prefill chunk reads and writes its one slot
    with the functions below."""

    n_layers: int
    slots: int
    heads: int
    d_k: int
    d_v: int
    conv_rows: int
    conv_channels: int

    @classmethod
    def for_model(cls, cfg, slots: int) -> Optional["StateStore"]:
        """The store a model's recurrent layers need; None for a model
        without. A Mamba layer is one head of [d_state, channels]."""
        kind = cfg.recurrent_kind
        if kind is None:
            return None
        if kind == "mamba":
            return cls(cfg.n_of_kind(kind), slots, 1, cfg.mamba_d_state,
                       cfg.mamba_inner, cfg.mamba_d_conv - 1, cfg.mamba_inner)
        return cls(cfg.n_of_kind(kind), slots, cfg.lin_heads, cfg.lin_dk,
                   cfg.lin_dv, cfg.lin_conv - 1, cfg.lin_conv_channels)

    @property
    def trash_slot(self) -> int:
        """Where an inactive slot's writes are steered: one past the slots."""
        return self.slots

    @property
    def state_shape(self) -> tuple:
        return (self.n_layers, self.slots + 1, self.heads, self.d_k, self.d_v)

    @property
    def conv_shape(self) -> tuple:
        return (self.n_layers, self.slots + 1, self.conv_rows, self.conv_channels)

    @property
    def slot_bytes(self) -> int:
        """Bytes ONE sequence's state takes over all the recurrent layers."""
        return 4 * self.n_layers * (
            self.heads * self.d_k * self.d_v + self.conv_rows * self.conv_channels)

    @property
    def bytes(self) -> int:
        return (self.slots + 1) * self.slot_bytes

    def fresh(self) -> tuple:
        """(state, conv tail), zeros."""
        import jax.numpy as jnp

        return (jnp.zeros(self.state_shape, jnp.float32),
                jnp.zeros(self.conv_shape, jnp.float32))


def read_slot(store, layer: int, slot, fresh):
    """One slot's entry of one of the two state arrays, ``[layer, slot]``
    (slot a traced scalar) — zeros where ``fresh``: a sequence's first chunk
    starts from nothing, whatever the slot's last owner left there."""
    import jax
    import jax.numpy as jnp

    # one slice of the whole store: ``store[layer]`` first would be a copy
    # of a layer's every slot
    got = jax.lax.dynamic_slice(
        store, (layer, slot) + (0,) * (store.ndim - 2), (1, 1) + store.shape[2:])
    return jnp.where(fresh, 0.0, got[0, 0])


def write_slot(store, layer: int, slot, value):
    """``store[layer, slot] = value``, where the store lies (a
    dynamic-update-slice of one slot's entry)."""
    import jax

    at = (layer, slot) + (0,) * value.ndim
    return jax.lax.dynamic_update_slice(store, value[None, None], at)


def pool_bytes(
    n_layers: int,
    num_pages: int,
    page_size: int,
    n_kv_heads: int,
    head_dim: int,
    dtype_bytes: int = 4,
    state: Optional[StateStore] = None,
) -> int:
    """Device bytes of the sequence state of both kinds: the K+V pools over
    the ``n_layers`` ATTENDING layers (including the trash page) and, for a
    model with recurrent layers, its ``StateStore`` — the number
    tools/memplan.py budgets for a serve job."""
    per_side = (
        n_layers * (num_pages + 1) * page_size * n_kv_heads * head_dim
    )
    return 2 * per_side * dtype_bytes + (state.bytes if state else 0)


def write_rows(kp, vp, layer: int, k, v, pid, row):
    """Store ``k[i]`` / ``v[i]`` ([n, n_kv_heads, head_dim]) at
    ``pool[layer, pid[i], :, row[i], :]`` and return the two pools —
    updated IN PLACE inside a compiled program whose caller donates them.

    One XLA scatter a side whose only window dimension is ``head_dim``,
    the pool's minor-most: the index arrays name (page, head, row), so
    the scatter stores ``n * n_kv_heads`` rows of ``head_dim`` values and
    is content with the pool's own head-major layout. Not
    ``kp.at[layer, pid, :, row].set(k)``: there the two index arrays
    straddle the head dimension, the scatter's window is (head,
    head_dim), XLA wants those two adjacent, puts the pool in a
    row-major-pages layout for the whole program and relays the WHOLE
    pool at the program's entry and back at its exit — four copies of a
    pool side to store ``n`` rows, and a slice of a whole layer in front
    of every kernel call (PERF.md §6, PR 25). Rows that share a target
    (inactive slots and padding, all steered to the trash page) land in
    no stated order; nobody reads them."""
    import jax.numpy as jnp

    at = (layer, pid[:, None], jnp.arange(k.shape[1])[None, :], row[:, None])
    return kp.at[at].set(k), vp.at[at].set(v)


@dataclass
class PagePool:
    """Free-list page allocator over a pool of ``num_pages`` pages.

    Pure host-side bookkeeping: the device pool itself is allocated by
    the engine (it owns dtype/layout); this class only decides which
    page ids are live. ``free_count`` is the leak probe — after every
    sequence is finished and freed it must equal ``num_pages``.
    ``peak_in_use`` (most pages ever held at once) and ``alloc_failures``
    (allocations refused) are counted as they happen; the engine writes
    both out with its own counters."""

    num_pages: int
    _free: List[int] = field(default_factory=list)
    peak_in_use: int = 0
    alloc_failures: int = 0

    def __post_init__(self) -> None:
        if self.num_pages < 1:
            raise ValueError(f"page pool needs >= 1 page, got {self.num_pages}")
        # LIFO: pop from the tail, so page 0 is handed out first.
        self._free = list(range(self.num_pages - 1, -1, -1))

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def trash_page(self) -> int:
        """The masked-write sink: one past the allocatable range."""
        return self.num_pages

    def alloc(self, n: int) -> List[int]:
        """Allocate ``n`` pages or raise PoolExhausted (all-or-nothing:
        a partial grant would leak on the caller's error path)."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            self.alloc_failures += 1
            raise PoolExhausted(
                f"need {n} pages, {len(self._free)}/{self.num_pages} free"
            )
        got = [self._free.pop() for _ in range(n)]
        self.peak_in_use = max(self.peak_in_use, self.num_pages - len(self._free))
        return got

    def free(self, pages: List[int]) -> None:
        """Return pages to the free list. Copy-free reuse: the device
        pages are NOT cleared — the next owner overwrites them and its
        page table masks anything it hasn't written yet."""
        for p in pages:
            if not 0 <= p < self.num_pages:
                raise ValueError(f"free of page {p} outside pool")
            if p in self._free:
                raise ValueError(f"double free of page {p}")
        self._free.extend(pages)


@dataclass
class SequencePages:
    """One sequence's page table: the ordered page ids backing positions
    [0, len). Grown on demand by the engine as the sequence crosses page
    boundaries; freed wholesale at completion."""

    page_size: int
    pages: List[int] = field(default_factory=list)

    @property
    def capacity(self) -> int:
        return len(self.pages) * self.page_size

    def ensure(self, length: int, pool: PagePool) -> None:
        """Grow to cover ``length`` positions (PoolExhausted propagates —
        the engine's admission policy reserves worst-case up front by
        default, so on-demand growth only fires under the optimistic
        knob)."""
        need = pages_needed(length, self.page_size) - len(self.pages)
        if need > 0:
            self.pages.extend(pool.alloc(need))

    def release(self, pool: PagePool) -> None:
        pool.free(self.pages)
        self.pages = []
