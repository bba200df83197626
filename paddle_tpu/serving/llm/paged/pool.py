"""PagePool + block tables: the paged KV memory substrate.

StaticKVCache gives every sequence a full ``[layers, max_seq, H, D]``
slot row for its whole lifetime — a 64-token chat in a 32k-max-seq fleet
wastes 99.8% of its reservation. This module rebuilds the substrate on
the vLLM/PagedAttention design: K and V live in ONE preallocated arena
of fixed-size token *pages*,

    arena[k|v] : [num_pages + 1, num_layers, page_size, H, D]

and each sequence owns a *block table* — a ``[pages_per_seq]`` int32
device row mapping logical page index -> physical arena page. Logical
row ``t`` of a sequence lives at ``arena[bt[t // page_size], :,
t % page_size]``. Pages are ref-counted on the host (a page shared by a
cached prefix and two live sequences has refcount 3), so prefix reuse is
a block-table splice (zero copied bytes) and divergence is a single-page
copy-on-write, not a whole-prefix copy.

The LAST physical page (index ``num_pages``) is the **trash page**: the
block tables of freed/unused slots point at it, and right-padded prefill
junk rows are routed to it, so every compiled program can write
unconditionally on uniform shapes (the LazyTensor one-program
discipline) while unmapped logical rows never corrupt live pages.
Whatever lands in the trash page is garbage by construction and every
read of it is masked by the per-slot length vector.

Host bookkeeping (free list, refcounts) mirrors StaticKVCache's slot
lifecycle; ``alloc``/``release`` never touch the device beyond the O(1)
block-table entry updates, which are jitted scalar scatters.

The arenas are updated IN PLACE. Every jitted program that takes them
(decode step, prefill, tail prefill, the speculative verify step, and
the one-page maintenance programs below) *donates* them and writes rows
by ``(page, layer, offset)`` into the whole 5-D array, so a tick moves
the rows it writes and not the arena. The arrays handed to such a call
are deleted by it: :class:`PagedKVCache` installs the outputs at once
(``swap``), and nothing else may keep a reference to ``kv.k``/``kv.v``
across a call.

Beside the pages a cache may hold further kinds of per-sequence state, for
models whose layers do not all keep keys and values: ``state`` is a dict of
named arrays (``state_rows``), each either a fixed-size row per SLOT
(``("slot", shape)`` -> ``[num_slots, *shape]``: a short convolution's last
inputs, a linear-attention layer's state) or a row per PAGE (``("page",
shape)`` -> ``[num_pages + 1, *shape]``: an index over a page's rows, such
as its compressed keys, which is then allocated, shared, freed and evicted
with the page, trash page included). A slot's row is addressed by slot: the
slot's prefill starts it afresh (so a reused slot starts from its own
prompt, never from the last tenant's) and freeing the slot frees it. Every
program that takes the arenas takes, donates and returns the whole dict with
them.

**Page groups.** Layers of one model need not keep the same pages of a
sequence: a sliding-window layer reads the last ``W`` rows only. A cache is
therefore a list of page groups (:class:`PageGroup`), each with its layers,
its own arena ``[pages + 1, len(layers), page, H, D]``, its own
:class:`PagePool`, block tables and trash page, and an optional window. A
group without a window keeps every page; a group with one maps only the
pages that hold rows a later query still reads (``ensure_pages``) and gives
back those that fall wholly behind the window (``release_behind``), during
decode and between the chunks of a prefill. The default, ONE group of all
the layers and no window, is the cache described above: ``kv.k``, ``kv.v``,
``kv.block_tables``, ``kv.pool`` are the first group's, which is all the GPT,
LFM2 and SALA programs read.

``fused_kv``: a head size under the 128-lane width (64, say) would give the
arena a minor axis the device lays out compactly, in another order than the
row-major one the paged kernel reads, and every program would copy the
whole arena in and out. Such a cache keeps ONE arena whose rows hold a
head's key and value side by side, ``[P+1, L, page, H, 2 * D]`` in ``k``
(``v`` is an empty placeholder): the same bytes a page, a 128-wide minor
axis, and one row read serves both. The decoder family asks for it, the
cache does not choose it from ``head_dim``: the GPT views below
(:class:`PagedRows`, the tail prefill's gather), the page copy and the export read
``kbuf`` and ``vbuf`` as two arenas, so a GPT with head size 64 still keeps
two (and pays that copy) until they read fused rows.

**Latent rows.** A family with latent attention keeps ONE row a token and
layer for ALL its heads, a compressed latent beside a shared rotary key part
(``models/moonlight.py``): a value is the row's first columns, so there is no
second arena to allocate, copy or export. Such a cache is ``fused_kv=True``
with the family's ``row_shape=(row,)``: one arena ``[P+1, L, page, row]``,
``row`` the latent and rotary widths and whatever padding the family lays
behind them; ``v`` is the empty placeholder. :meth:`PagedKVCache.row_nbytes`
is what a token and layer hold, padding included.
"""
from __future__ import annotations

import functools
import heapq
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ....models.gpt import masked_attention
from ....ops.paged_attention import paged_attention
from ..kvcache import (SlotsExhausted, dequantize_kv, is_quantized_kv,
                       kv_nbytes, per_slot, quantize_kv_rows, valid_mask)


class PagesExhausted(RuntimeError):
    """The pool cannot satisfy an allocation (callers should gate on
    :attr:`PagePool.free_pages` / evict before hitting this)."""


class PagePool:
    """Host-side free list + per-page refcounts over the physical pages.

    A page is *free* when its refcount is 0. ``alloc`` hands out the
    lowest free index (deterministic tests) at refcount 1; ``retain``
    adds a sharer; ``release`` drops one reference and returns the page
    to the free list when the count hits zero. Releasing a free page
    raises — the page-level double-free guard the leak tests pin.
    """

    def __init__(self, num_pages: int):
        if num_pages < 1:
            raise ValueError(f"need num_pages >= 1, got {num_pages}")
        self.num_pages = int(num_pages)
        self._refs = np.zeros(self.num_pages, np.int64)
        self._free: List[int] = list(range(self.num_pages))
        heapq.heapify(self._free)
        #: lifetime counters — the leak invariant is
        #: ``total_allocs + total_retains == total_releases`` once every
        #: sequence/prefix-entry is gone (pages_in_use == 0)
        self.total_allocs = 0
        self.total_retains = 0
        self.total_releases = 0
        self.peak_in_use = 0

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def pages_in_use(self) -> int:
        return self.num_pages - len(self._free)

    def refcount(self, pid: int) -> int:
        return int(self._refs[pid])

    def alloc_many(self, n: int) -> List[int]:
        """Claim ``n`` fresh pages (refcount 1 each), atomically: either
        all ``n`` allocate or none do and :class:`PagesExhausted` is
        raised — a partial allocation would leak on the error path."""
        if n < 0:
            raise ValueError(f"alloc_many({n})")
        if n > len(self._free):
            raise PagesExhausted(
                f"need {n} pages, only {len(self._free)} of "
                f"{self.num_pages} free")
        out = [heapq.heappop(self._free) for _ in range(n)]
        for pid in out:
            self._refs[pid] = 1
        self.total_allocs += n
        self.peak_in_use = max(self.peak_in_use, self.pages_in_use)
        return out

    def alloc(self) -> int:
        return self.alloc_many(1)[0]

    def retain(self, pid: int):
        """Add a reference to an already-live page (prefix sharing)."""
        if not (0 <= pid < self.num_pages):
            raise ValueError(f"retain of non-pool page {pid}")
        if self._refs[pid] <= 0:
            raise ValueError(f"retain of free page {pid}")
        self._refs[pid] += 1
        self.total_retains += 1

    def release(self, pid: int) -> bool:
        """Drop one reference; returns True when the page went back to
        the free list. Raises on over-release (page double-free)."""
        if not (0 <= pid < self.num_pages):
            raise ValueError(f"release of non-pool page {pid}")
        if self._refs[pid] <= 0:
            raise ValueError(
                f"page {pid} double-free: released with refcount 0")
        self._refs[pid] -= 1
        self.total_releases += 1
        if self._refs[pid] == 0:
            heapq.heappush(self._free, pid)
            return True
        return False

    def reset(self):
        self._refs[:] = 0
        self._free = list(range(self.num_pages))
        heapq.heapify(self._free)

    def __repr__(self):
        return (f"PagePool(pages={self.num_pages}, "
                f"in_use={self.pages_in_use}, "
                f"allocs={self.total_allocs}, "
                f"releases={self.total_releases})")


# -- jitted block-table / arena maintenance ops ------------------------------
# Scalar-indexed so ONE trace serves every (slot, idx, pid) triple; an
# eager `.at[3, 2].set(7)` would bake the constants in and compile a
# fresh executable per distinct index pair.

@jax.jit
def _bt_set_entry(bt, slot, idx, pid):
    return bt.at[slot, idx].set(pid)


@jax.jit
def _bt_set_entries(bt, slot, start, pids, n):
    """Entries ``[start, start + n)`` of a slot's row from the first ``n``
    of ``pids`` (padded to the row's length): a whole prompt's pages in one
    dispatch."""
    idx = jnp.arange(bt.shape[1])
    fresh = jnp.take(pids, jnp.clip(idx - start, 0, pids.shape[0] - 1))
    return bt.at[slot].set(jnp.where((idx >= start) & (idx < start + n),
                                     fresh, bt[slot]))


@jax.jit
def _bt_reset_row(bt, slot, fill):
    return bt.at[slot].set(fill)


@functools.partial(jax.jit, donate_argnums=0)
def _arena_copy_page(buf, dst, src):
    """Copy physical page ``src`` -> ``dst`` (both arenas' leaves): the
    copy-on-write split. One traced program per arena shape; ``buf`` is
    donated, so one page moves and not the arena."""
    def _cp(x):
        row = jax.lax.dynamic_index_in_dim(x, src, axis=0, keepdims=True)
        return jax.lax.dynamic_update_slice_in_dim(x, row, dst, axis=0)
    return jax.tree_util.tree_map(_cp, buf)


@functools.partial(jax.jit, donate_argnums=0)
def _arena_write_page(buf, dst, page):
    """Install one host-shipped physical page at index ``dst`` — the
    import half of sequence migration. ``page`` carries a single page's
    rows per leaf (``[L, page, H, D]``, or the quantized ``q``/``s``
    pair); scalar-indexed so one traced program serves every dst.
    ``buf`` is donated."""
    def _wr(x, p):
        return jax.lax.dynamic_update_slice_in_dim(
            x, p[None].astype(x.dtype), dst, axis=0)
    return jax.tree_util.tree_map(_wr, buf, page)


@jax.jit
def _len_set(lengths, slot, n):
    return lengths.at[slot].set(n)


# -- functional writers / readers (used inside jitted programs) --------------

def paged_write_rows(buf, rows, pids, ppos, layer):
    """Write one K or V row per entry into layer ``layer`` of a whole
    arena, where it lies.

    ``buf``: ``[P+1, L, page, H, D]`` (or the quantized dict);
    ``rows``: ``[N, H, D]``; ``pids``/``ppos``: ``[N]`` int32 physical
    page + in-page offset. Rows routed to the trash page may collide —
    they are junk by construction. One scatter per leaf, and no view of
    the layer: XLA updates a donated arena in place."""
    if is_quantized_kv(buf):
        qs = quantize_kv_rows(rows)            # q [N, H, D], s [N]
        return {"q": buf["q"].at[pids, layer, ppos].set(qs["q"]),
                "s": buf["s"].at[pids, layer, ppos].set(qs["s"])}
    return buf.at[pids, layer, ppos].set(rows)


def paged_write_prompt_rows(buf, rows, pids, ppos):
    """Write ``N`` tokens' rows across ALL layers at once into a whole
    arena. ``buf``: ``[P+1, L, page, H, D]`` (or dict); ``rows``:
    ``[N, L, H, D]`` — token ``n``'s layer-``l`` row lands at
    ``buf[pids[n], l, ppos[n]]``. One scatter per leaf covers the whole
    prompt x layers block (the no-per-layer-host-loop invariant)."""
    num_layers = rows.shape[1]
    li = jnp.arange(num_layers, dtype=jnp.int32)[None, :]      # [1, L]
    pi = pids[:, None]                                         # [N, 1]
    oi = ppos[:, None]
    if is_quantized_kv(buf):
        qs = quantize_kv_rows(rows)            # q [N, L, H, D], s [N, L]
        return {"q": buf["q"].at[pi, li, oi].set(qs["q"]),
                "s": buf["s"].at[pi, li, oi].set(qs["s"])}
    return buf.at[pi, li, oi].set(rows)


def paged_gather_rows(buf, block_tables, layer):
    """Reconstruct contiguous logical rows of layer ``layer`` from a
    whole arena: ``[P+1, L, page, H, D]`` gathered through ``[S, PP]``
    block tables -> ``[S, PP*page, H, D]`` — shape-identical to a slot
    buffer's layer view, which is what makes the gather attention lane
    bitwise-equal to the slot path. One gather by (page, layer): the
    layer is never cut out of the arena first."""
    if is_quantized_kv(buf):
        q = buf["q"][block_tables, layer]      # [S, PP, page, H, D]
        s = buf["s"][block_tables, layer]      # [S, PP, page]
        sh = q.shape
        return {"q": q.reshape(sh[0], sh[1] * sh[2], sh[3], sh[4]),
                "s": s.reshape(sh[0], sh[1] * sh[2])}
    g = buf[block_tables, layer]
    sh = g.shape
    return g.reshape(sh[0], sh[1] * sh[2], sh[3], sh[4])


def paged_row_index(block_tables, positions, page_size):
    """(physical page, in-page offset) of logical rows ``positions``
    (``[S]`` or ``[S, T]``) of each slot, both flattened to ``[S * T]``.
    Out-of-range positions (inactive slots whose lengths keep advancing)
    clip to the last table entry, which for a freed slot is the trash
    page — the paged analogue of the slot path's clamped
    ``dynamic_update_slice`` on inactive rows."""
    positions = per_slot(positions, 0)
    idx = jnp.clip(positions // page_size, 0, block_tables.shape[1] - 1)
    pid = jnp.take_along_axis(block_tables, idx, axis=1)
    return pid.reshape(-1), (positions % page_size).reshape(-1)


def paged_write_prompts(buf, rows, block_tables, slot_ids, starts, lens,
                        page_size):
    """Scatter whole prompts (or prompt tails) into a whole arena, one
    :func:`paged_write_prompt_rows` per request: request ``i``'s row ``t``
    of ``rows`` ``[B, T, L, H, D]`` lands at logical position
    ``starts[i] + t`` of slot ``slot_ids[i]``; right-padding (``t >=
    lens[i]``) goes to the trash page instead of being parked past the
    slot length."""
    trash = jax.tree_util.tree_leaves(buf)[0].shape[0] - 1
    last_page = block_tables.shape[1] - 1
    t = jnp.arange(rows.shape[1], dtype=jnp.int32)
    for i in range(rows.shape[0]):
        pos = starts[i] + t
        bt_row = block_tables[slot_ids[i]]                     # [PP]
        pid = jnp.where(
            t < lens[i],
            bt_row[jnp.clip(pos // page_size, 0, last_page)], trash)
        buf = paged_write_prompt_rows(buf, rows[i], pid, pos % page_size)
    return buf


# -- cache views of models.gpt.gpt_block (used inside jitted programs) -------

class PagedRows:
    """The paged twin of :class:`~..kvcache.SlotRows` (same ``positions``
    and row shapes): layer ``li`` scatters its rows through the block
    tables into the whole arenas, where they lie, then attends.
    ``attn_impl="gather"`` gathers the slots' logical rows back (int8
    pages dequantised here) and runs the slot path's exact attention under
    the validity mask; ``"kernel"`` (the decode tick, dense arenas) hands
    the arenas to the Pallas ``paged_attention``, which walks the block
    table itself. Holds the (traced) arenas and replaces them as layers
    write: read ``kbuf`` and ``vbuf`` back when the layers are done."""

    def __init__(self, kbuf, vbuf, block_tables, positions, page_size,
                 attn_impl, dtype):
        self.kbuf, self.vbuf = kbuf, vbuf
        self.block_tables, self.positions = block_tables, positions
        self.attn_impl = attn_impl
        self.pid, self.ppos = paged_row_index(block_tables, positions,
                                              page_size)
        self.mask = None if attn_impl == "kernel" else valid_mask(
            positions, block_tables.shape[1] * page_size, dtype)

    def attend(self, li, q, k, v, scale):
        flat = (-1,) + k.shape[-2:]                            # [S*T, H, D]
        self.kbuf = paged_write_rows(self.kbuf, k.reshape(flat), self.pid,
                                     self.ppos, li)
        self.vbuf = paged_write_rows(self.vbuf, v.reshape(flat), self.pid,
                                     self.ppos, li)
        if self.attn_impl == "kernel":
            return paged_attention(q, self.kbuf, self.vbuf,
                                   self.block_tables, self.positions,
                                   layer=li, scale=scale)
        kd = dequantize_kv(
            paged_gather_rows(self.kbuf, self.block_tables, li), q.dtype)
        vd = dequantize_kv(
            paged_gather_rows(self.vbuf, self.block_tables, li), q.dtype)
        return masked_attention(q, kd, vd, self.mask, scale)


def pages_for_tokens(n_tokens: int, page_size: int) -> int:
    """ceil(n_tokens / page_size) — the admission math helper."""
    return -(-int(n_tokens) // int(page_size))


@dataclass(frozen=True)
class PageGroup:
    """Layers that keep the same pages of a sequence: ``layers`` (the
    model's indices, in arena order), ``window`` (None: every page; ``W``:
    the pages that hold the last ``W`` rows) and ``num_pages`` (None: the
    worst case, every slot at its most)."""
    layers: Tuple[int, ...]
    window: Optional[int] = None
    num_pages: Optional[int] = None


class _GroupState:
    """One group's device arrays and host bookkeeping. ``slot_pages[s]``
    are the physical pages of slot ``s``'s logical pages ``first[s] ..``
    (``first`` stays 0 in a group without a window)."""

    def __init__(self, group: PageGroup, num_slots: int, pages_per_seq: int,
                 num_pages: int, zero_buf, v_buf):
        self.layers, self.window = tuple(group.layers), group.window
        self.num_pages = int(num_pages)
        self.trash = self.num_pages            # physical junk-sink page
        self.k = zero_buf(self.num_pages + 1, len(self.layers))
        self.v = v_buf(self.num_pages + 1, len(self.layers))
        self.block_tables = jnp.full((num_slots, pages_per_seq), self.trash,
                                     jnp.int32)
        self.pool = PagePool(self.num_pages)
        self.slot_pages: List[List[int]] = [[] for _ in range(num_slots)]
        self.first: List[int] = [0] * num_slots

    def reset_tables(self):
        self.block_tables = jnp.full(self.block_tables.shape, self.trash,
                                     jnp.int32)


def window_page_bound(window: int, span: int, page_size: int) -> int:
    """The most pages a window group maps for one slot at any moment, when
    at most ``span`` rows are written between two ``release_behind``: the
    window's rows, the rows being written, and a page's slack at either
    end."""
    return -(-(window + span) // page_size) + 2


class PagedKVCache:
    """Paged per-slot KV storage: one shared page arena + per-slot block
    tables + the same device ``lengths`` vector StaticKVCache threads.

    ``k``/``v``: ``[num_pages + 1, num_layers, page_size, H, D]`` device
    arenas (index ``num_pages`` is the trash page). ``block_tables``:
    ``[num_slots, pages_per_seq]`` int32 device array (unmapped entries
    point at the trash page). The host tracks which physical pages each
    slot holds references on (``_slot_pages``); ``free`` releases them
    back to the :class:`PagePool`.
    """

    def __init__(self, num_slots: int, num_layers: int, max_seq: int,
                 num_heads: int, head_dim: int, dtype="float32",
                 kv_dtype: Optional[str] = None, page_size: int = 16,
                 num_pages: Optional[int] = None,
                 state_rows: Optional[Dict[str, Tuple]] = None,
                 fused_kv: bool = False,
                 row_shape: Optional[Tuple[int, ...]] = None,
                 groups: Optional[Sequence[PageGroup]] = None):
        if num_slots < 1 or max_seq < 2:
            raise ValueError(
                f"need num_slots >= 1 and max_seq >= 2, got "
                f"{num_slots}/{max_seq}")
        if kv_dtype not in (None, "int8"):
            raise ValueError(
                f"kv_dtype must be None (dense) or 'int8', got "
                f"{kv_dtype!r}")
        if page_size < 1 or max_seq % page_size:
            raise ValueError(
                f"page_size {page_size} must divide max_seq {max_seq} — "
                f"equal logical rows are what make paged decode "
                f"bitwise-comparable to the slot path")
        self.num_slots = int(num_slots)
        self.num_layers = int(num_layers)
        self.max_seq = int(max_seq)
        self.num_heads = int(num_heads)
        self.head_dim = int(head_dim)
        self.page_size = int(page_size)
        self.pages_per_seq = self.max_seq // self.page_size
        if groups is None:      # today's cache: every layer, every page
            groups = [PageGroup(tuple(range(self.num_layers)), None,
                                num_pages)]
        elif num_pages is not None:
            raise ValueError("give each group its own num_pages")
        if sorted(i for g in groups for i in g.layers) != list(
                range(self.num_layers)):
            raise ValueError(
                f"the groups' layers {[g.layers for g in groups]} are not "
                f"the {self.num_layers} layers, each once")
        self.dtype = jnp.dtype(dtype)
        self.kv_dtype = kv_dtype
        self.quantized = kv_dtype == "int8"
        self.fused_kv = bool(fused_kv)
        if self.fused_kv and self.quantized:
            raise ValueError("fused K|V rows are dense only")
        # ``row_shape``: a family's own layout of one row of one layer (a
        # head-major arena counts its heads among the layers and keeps
        # ``(2 * D,)`` rows); the default is ``[heads, D]``
        row = tuple(row_shape) if row_shape is not None else (
            self.num_heads, self.head_dim * (2 if fused_kv else 1))

        def _zero_buf(pages, layers):
            shape = (pages, layers, self.page_size) + row
            if self.quantized:
                return {"q": jnp.zeros(shape, jnp.int8),
                        "s": jnp.zeros(shape[:3], jnp.float32)}
            return jnp.zeros(shape, self.dtype)

        def _v_buf(pages, layers):
            return (jnp.zeros((0,), self.dtype) if fused_kv
                    else _zero_buf(pages, layers))

        #: the page groups, the first of them behind ``k``/``v``/
        #: ``block_tables``/``pool``
        self.groups: List[_GroupState] = []
        for g in groups:
            if g.window is not None and g.window < 1:
                raise ValueError(f"window {g.window} < 1")
            n = g.num_pages
            if n is None:
                # worst case: every slot fully grown — byte parity with the
                # static cache; real deployments size this far smaller (a
                # family sizes its window groups by their bound)
                n = self.num_slots * self.pages_per_seq
            least = self.pages_per_seq if g.window is None else min(
                self.pages_per_seq, pages_for_tokens(g.window,
                                                     self.page_size) + 1)
            if n < least:
                raise ValueError(
                    f"num_pages {n} cannot hold even one full "
                    f"sequence ({least} pages)")
            self.groups.append(_GroupState(g, self.num_slots,
                                           self.pages_per_seq, n, _zero_buf,
                                           _v_buf))
        #: named state beside the pages (None: K and V only)
        leading = {"slot": self.num_slots, "page": self.num_pages + 1}
        self.state = None if not state_rows else {
            name: jnp.zeros((leading[per],) + tuple(shape), self.dtype)
            for name, (per, shape) in state_rows.items()}
        self.lengths = jnp.zeros((self.num_slots,), jnp.int32)
        # both page-mapping programs compile here, in set-up, whichever of
        # them a cell's first admissions happen to need (they write the
        # trash page's id where it already stands)
        self.block_tables = _bt_set_entry(self.block_tables, 0, 0, self.trash)
        self.block_tables = _bt_set_entries(
            self.block_tables, 0, 0,
            jnp.full((self.pages_per_seq,), self.trash, jnp.int32), 0)
        self._free: List[int] = list(range(self.num_slots))
        self._active: set = set()
        #: copy-on-write splits performed (admission divergence)
        self.cow_splits = 0
        #: pages window groups gave back behind their windows
        self.window_released = 0

    # -- the first group, under the names a cache of one group has ------------
    k = property(lambda self: self.groups[0].k,
                 lambda self, x: setattr(self.groups[0], "k", x))
    v = property(lambda self: self.groups[0].v,
                 lambda self, x: setattr(self.groups[0], "v", x))
    block_tables = property(
        lambda self: self.groups[0].block_tables,
        lambda self, x: setattr(self.groups[0], "block_tables", x))
    pool = property(lambda self: self.groups[0].pool)
    num_pages = property(lambda self: self.groups[0].num_pages)
    trash = property(lambda self: self.groups[0].trash)
    _slot_pages = property(lambda self: self.groups[0].slot_pages)

    # -- slot lifecycle (host side) ------------------------------------------
    @property
    def free_slots(self) -> int:
        return len(self._free)

    @property
    def active_slots(self) -> Tuple[int, ...]:
        return tuple(sorted(self._active))

    def alloc(self) -> int:
        if not self._free:
            raise SlotsExhausted(
                f"all {self.num_slots} KV slots are in use")
        slot = self._free.pop(0)
        self._active.add(slot)
        return slot

    def free(self, slot: int):
        """Return a slot AND its page references to the pools. Raises on
        a slot double-free — handing one slot (and its pages) to two
        sequences is the corruption StaticKVCache.free guards against."""
        if not (0 <= slot < self.num_slots) or slot not in self._active:
            raise ValueError(
                f"slot {slot} is not active (double free?)")
        self._active.discard(slot)
        for g in self.groups:
            for pid in g.slot_pages[slot]:
                g.pool.release(pid)
            g.slot_pages[slot], g.first[slot] = [], 0
            g.block_tables = _bt_reset_row(g.block_tables, slot, g.trash)
        self._free.append(slot)
        self._free.sort()

    def reset(self):
        """Free every slot, every page reference, and zero the lengths
        (arenas are left as is — lengths + trash routing gate validity).
        For warmup and engine restarts."""
        for slot in list(self._active):
            self.free(slot)
        self._free = list(range(self.num_slots))
        self._active.clear()
        for g in self.groups:
            g.slot_pages = [[] for _ in range(self.num_slots)]
            g.first = [0] * self.num_slots
            g.pool.reset()
            g.reset_tables()
        self.lengths = jnp.zeros((self.num_slots,), jnp.int32)

    # -- page mapping (host decides, device block table records) -------------
    def mapped_pages(self, slot: int) -> int:
        return len(self._slot_pages[slot])

    def mapped_tokens(self, slot: int) -> int:
        return len(self._slot_pages[slot]) * self.page_size

    def slot_page_ids(self, slot: int) -> Tuple[int, ...]:
        return tuple(self._slot_pages[slot])

    @property
    def has_window(self) -> bool:
        return any(g.window is not None for g in self.groups)

    def _map_page(self, slot: int, pid: int):
        idx = len(self._slot_pages[slot])
        if idx >= self.pages_per_seq:
            raise ValueError(
                f"slot {slot} already maps {idx} pages (max_seq reached)")
        self._slot_pages[slot].append(pid)
        self.block_tables = _bt_set_entry(self.block_tables, slot, idx,
                                          pid)

    def ensure_pages(self, slot: int, n_tokens: int,
                     windowed: bool = True) -> int:
        """Map fresh pages so logical rows ``[0, n_tokens)`` are backed, in
        every group: from the slot's first page in a group that keeps them
        all, and behind what the group has mapped so far in a group with a
        window (what :meth:`release_behind` gave back stays given back).
        ``windowed=False`` leaves the window groups alone: a chunked
        admission reserves the whole prompt where every page is kept and
        maps a window group's pages as the chunks advance. Returns how many
        pages were newly allocated. Atomic: raises :class:`PagesExhausted`
        without mapping anything when a pool cannot cover its group's need
        (callers evict and retry)."""
        need = pages_for_tokens(n_tokens, self.page_size)
        plan = []
        for g in self.groups:
            start = g.first[slot] + len(g.slot_pages[slot])
            if need > start and (windowed or g.window is None):
                plan.append((g, start, need - start))
        if not plan:
            return 0
        if need > self.pages_per_seq:
            raise ValueError(
                f"slot {slot} would map {need} pages (max_seq reached)")
        for g, _, n in plan:
            if n > g.pool.free_pages:
                raise PagesExhausted(
                    f"need {n} pages, only {g.pool.free_pages} of "
                    f"{g.pool.num_pages} free")
        for g, start, n in plan:
            fresh = g.pool.alloc_many(n)
            g.slot_pages[slot].extend(fresh)
            if n == 1:      # a decode step's next page: scalars only
                g.block_tables = _bt_set_entry(g.block_tables, slot, start,
                                               fresh[0])
            else:       # a prompt's pages: one dispatch, not one a page
                padded = np.full(self.pages_per_seq, g.trash, np.int32)
                padded[:n] = fresh
                g.block_tables = _bt_set_entries(
                    g.block_tables, slot, start, jnp.asarray(padded), n)
        return sum(n for _, _, n in plan)

    def _release_pages(self, g: _GroupState, slot: int, n: int):
        """Give the first ``n`` mapped pages of ``slot`` in group ``g`` back
        to its pool and point their table entries at the trash page, in one
        dispatch."""
        if n <= 0:
            return
        for pid in g.slot_pages[slot][:n]:
            g.pool.release(pid)
        del g.slot_pages[slot][:n]
        g.block_tables = _bt_set_entries(
            g.block_tables, slot, g.first[slot],
            jnp.full((self.pages_per_seq,), g.trash, jnp.int32), n)
        g.first[slot] += n
        self.window_released += n

    def release_behind(self, slot: int, next_row: int) -> int:
        """The next query of ``slot`` is at row ``next_row`` or later: every
        window group returns the pages that lie wholly before the first row
        that query reads, ``next_row - W + 1``. Returns the pages released
        (0 in a cache whose groups keep every page)."""
        released = 0
        for g in self.groups:
            if g.window is None:
                continue
            keep_from = max(0, next_row - g.window + 1) // self.page_size
            n = min(keep_from - g.first[slot], len(g.slot_pages[slot]))
            self._release_pages(g, slot, n)
            released += max(n, 0)
        return released

    def window_pages(self) -> Tuple[int, int]:
        """``(held, unbounded)``: the pages the window groups hold mapped,
        and what they would hold if they kept every page of the same
        sequences."""
        held = unbounded = 0
        for g in self.groups:
            if g.window is not None:
                for first, pids in zip(g.first, g.slot_pages):
                    held += len(pids)
                    unbounded += (first + len(pids)) if pids else 0
        return held, unbounded

    def groups_short(self, n_tokens: int, span: int, reserve: int) -> bool:
        """Whether a group beyond the first lacks what admitting a prompt of
        ``n_tokens`` (``span`` rows written at a time) needs, ``reserve``
        pages kept back: the prompt's pages in a group that keeps them all;
        in a window group its bound, out of what the pool has free less
        what the slots already admitted may still claim up to theirs (a
        window group's pages are mapped as a prompt advances, not at its
        admission). The first group is the admission's own page math."""
        pages = pages_for_tokens(n_tokens, self.page_size)
        for g in self.groups[1:]:
            need, free = pages, g.pool.free_pages
            if g.window is not None:
                bound = min(self.pages_per_seq, window_page_bound(
                    g.window, span, self.page_size))
                need = min(pages, bound)
                free -= sum(max(0, bound - len(g.slot_pages[s]))
                            for s in self._active)
            if need + reserve > free:
                return True
        return False

    def adopt_shared_page(self, slot: int, pid: int):
        """Splice an already-live page (a prefix-store page) into the
        slot's block table at the next logical index: refcount +1, zero
        bytes copied."""
        self.pool.retain(pid)
        self._map_page(slot, pid)

    def adopt_copied_page(self, slot: int, src_pid: int) -> int:
        """Copy-on-write split: allocate a private page, device-copy the
        shared page's rows into it (in place: the arenas are donated),
        and map it. The new occupant can now write its divergent tail
        rows without touching sharers."""
        pid = self.pool.alloc()
        dst = jnp.asarray(pid, jnp.int32)
        src = jnp.asarray(src_pid, jnp.int32)
        self.k = _arena_copy_page(self.k, dst, src)
        self.v = _arena_copy_page(self.v, dst, src)
        self._map_page(slot, pid)
        self.cow_splits += 1
        return pid

    # -- sequence migration (cold path: export / import) ---------------------
    def read_pages(self, page_ids) -> Tuple[object, object]:
        """Host copies of the K and V arena rows for ``page_ids`` — the
        export half of sequence migration. One gather + one transfer per
        arena leaf (``[n, L, page, H, D]`` stacked over the requested
        pages, or the quantized ``q``/``s`` pair). Runs between decode
        ticks on the engine worker, never inside one."""
        idx = jnp.asarray([int(p) for p in page_ids], jnp.int32)

        def _take(buf):
            return jax.tree_util.tree_map(
                lambda x: np.asarray(jax.device_get(jnp.take(x, idx, axis=0))),  # noqa: PTA002 -- sequence-export page fetch: a deliberate once-per-migration transfer on the between-tick control path
                buf)
        # a cache of one arena (fused or latent rows) has no second one
        return _take(self.k), None if self.fused_kv else _take(self.v)

    def write_page(self, pid: int, k_page, v_page):
        """Install one host-shipped page (a ``read_pages`` row) at
        physical index ``pid`` — the import half of migration. The page
        must already be owned by the caller (allocated/mapped); sharers
        would observe the write."""
        if self.pool.refcount(pid) != 1:
            raise ValueError(
                f"write_page({pid}): refcount "
                f"{self.pool.refcount(pid)} != 1 — importing into a "
                f"shared or free page would corrupt sharers")
        dst = jnp.asarray(pid, jnp.int32)
        self.k = _arena_write_page(self.k, dst, k_page)
        if not self.fused_kv:
            self.v = _arena_write_page(self.v, dst, v_page)

    def set_length(self, slot: int, n_tokens: int):
        """Install a migrated sequence's resume position in the device
        lengths vector (the next decode step's write coordinate)."""
        if not (0 <= n_tokens <= self.max_seq):
            raise ValueError(f"set_length({slot}, {n_tokens})")
        self.lengths = _len_set(self.lengths, jnp.asarray(slot, jnp.int32),
                                jnp.asarray(n_tokens, jnp.int32))

    # -- state threading -----------------------------------------------------
    def swap(self, k, v, lengths, state=None):
        """Install the arrays returned by a jitted prefill/decode call —
        the same buffers the call was given, updated in place (the
        arrays it was given are deleted by the donation). Shape-checked:
        a shape change would mean a recompile upstream."""
        def _shapes(buf):
            return [leaf.shape for leaf in jax.tree_util.tree_leaves(buf)]
        assert _shapes(k) == _shapes(self.k) \
            and _shapes(v) == _shapes(self.v) \
            and _shapes(state) == _shapes(self.state), \
            (_shapes(k), _shapes(self.k), _shapes(state))
        self.k, self.v, self.lengths, self.state = k, v, lengths, state

    def swap_groups(self, ks, vs, lengths):
        """:meth:`swap` for a cache of several groups: ``ks[i]``, ``vs[i]``
        are group ``i``'s arenas as a program returned them."""
        assert [a.shape for a in ks] == [g.k.shape for g in self.groups] \
            and [a.shape for a in vs] == [g.v.shape for g in self.groups]
        for g, k, v in zip(self.groups, ks, vs):
            g.k, g.v = k, v
        self.lengths = lengths

    def state_bytes(self, name: Optional[str] = None) -> int:
        """Device bytes of the state beside the pages, or of the one named
        (0 without it)."""
        held = self.state or {}
        return sum(int(v.nbytes) for k, v in held.items()
                   if name is None or k == name)

    def kv_bytes(self) -> int:
        """Device bytes held by the K+V arenas (trash page included).
        Shape arithmetic only, so it is safe from any thread: an array
        a program has just taken by donation still knows its size."""
        return sum(kv_nbytes(g.k) + kv_nbytes(g.v) for g in self.groups)

    def group_bytes(self, windowed: bool) -> int:
        """Device bytes of the groups with a window, or of those without."""
        return sum(kv_nbytes(g.k) + kv_nbytes(g.v) for g in self.groups
                   if (g.window is not None) == windowed)

    def page_nbytes(self) -> int:
        """Device bytes of ONE physical page across both arenas and all
        layers — the unit the bytes_shared/bytes_copied counters count."""
        return (kv_nbytes(self.k) + kv_nbytes(self.v)) // (self.num_pages + 1)

    def row_nbytes(self) -> int:
        """Device bytes ONE token holds in one layer of the first group, as
        the arenas keep it (padding and every head included)."""
        g = self.groups[0]
        return self.page_nbytes() // (len(g.layers) * self.page_size)

    def host_lengths(self) -> np.ndarray:
        """One deliberate device->host fetch of the per-slot lengths
        (tests and ``/statsz`` only, never the per-tick path)."""
        return np.asarray(jax.device_get(self.lengths))  # noqa: PTA002 -- deliberate observability fetch (tests, /statsz); the tick loop never calls this

    def __repr__(self):
        return (f"PagedKVCache(slots={self.num_slots}, "
                f"layers={self.num_layers}, max_seq={self.max_seq}, "
                f"page={self.page_size}, pages={self.num_pages}, "
                f"in_use={self.pool.pages_in_use}, "
                f"active={len(self._active)})")
