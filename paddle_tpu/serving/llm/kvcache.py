"""StaticKVCache: preallocated slot-structured KV buffers for decode.

The concat-grown cache (``MultiHeadAttention.Cache``) changes shape every
token, so XLA specializes a new executable per length — the per-token
recompile flagged in ROADMAP item 1. This cache fixes every shape up
front: K and V live in ``[num_slots, num_layers, max_seq, heads,
head_dim]`` buffers, a sequence occupies one *slot* row for its whole
lifetime, and all writes are functional ``lax.dynamic_update_slice``
updates inside the jitted prefill/decode programs — the arrays never
change shape, so one compiled decode step serves every token of every
request (LazyTensor's keep-one-program-hot discipline, arxiv 2102.13267).

Slot lifecycle (host-side bookkeeping; device arrays are only ever
*replaced* by the functional step outputs):

    free ──alloc()──> active ──free()──> free
                (prefill writes [0, L))   (buffers keep stale rows; the
                                           per-slot length masks them and
                                           the next prefill overwrites)

The length vector lives on device (it is an input of the compiled step);
``alloc``/``free`` only mutate the host free-list, so slot churn costs no
host↔device traffic beyond the admission-time prompt upload.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...models.gpt import masked_attention


class SlotsExhausted(RuntimeError):
    """alloc() called with every slot in use (callers should gate on
    :attr:`StaticKVCache.free_slots` instead of catching this)."""


# -- int8 KV representation ---------------------------------------------------
# A quantized buffer is a dict pytree {"q": int8 [..., H, D] codes,
# "s": f32 [...] per-row absmax scales} — one scale per (slot, layer,
# position) row, so a loud token cannot flatten its neighbours'
# resolution. Dequant is q/127*s, computed INSIDE the fused decode step
# (the codes never round-trip through the host). Dicts are pytrees, so
# the quantized buffers flow through jax.jit/device_put exactly like the
# dense arrays they replace.

def quantize_kv_rows(x):
    """``[..., H, D]`` float rows -> ({int8 codes, f32 scales}) with one
    absmax scale per row (all leading axes)."""
    absmax = jnp.max(jnp.abs(x), axis=(-2, -1))
    s = jnp.where(absmax > 0, absmax, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(x / s[..., None, None] * 127.0),
                 -127.0, 127.0).astype(jnp.int8)
    return {"q": q, "s": s}


def dequantize_kv(buf, dtype=jnp.float32):
    """Dense view of a quantized buffer (or identity on a dense one)."""
    if not isinstance(buf, dict):
        return buf
    return (buf["q"].astype(dtype)
            * (buf["s"][..., None, None] / 127.0).astype(dtype))


def is_quantized_kv(buf) -> bool:
    return isinstance(buf, dict)


def kv_layer_view(buf, li: int):
    """Layer ``li``'s slice of a whole-cache buffer: dense
    ``[S, L, max, H, D] -> [S, max, H, D]``, quantized dict likewise on
    both leaves."""
    if isinstance(buf, dict):
        return {"q": buf["q"][:, li], "s": buf["s"][:, li]}
    return buf[:, li]


def kv_stack_layers(bufs):
    """Inverse of :func:`kv_layer_view` over all layers: re-stack the
    per-layer buffers on axis 1."""
    if bufs and isinstance(bufs[0], dict):
        return {"q": jnp.stack([b["q"] for b in bufs], axis=1),
                "s": jnp.stack([b["s"] for b in bufs], axis=1)}
    return jnp.stack(bufs, axis=1)


def kv_max_seq(buf) -> int:
    return (buf["q"] if isinstance(buf, dict) else buf).shape[2]


def kv_nbytes(buf) -> int:
    """Device bytes of a (possibly quantized) KV buffer."""
    return sum(int(leaf.nbytes)
               for leaf in jax.tree_util.tree_leaves(buf))


class StaticKVCache:
    """Preallocated per-slot KV storage + per-slot length/position state.

    ``k``/``v``: ``[num_slots, num_layers, max_seq, heads, head_dim]``
    device arrays. ``lengths``: ``[num_slots]`` int32 device vector — the
    number of valid cache rows per slot (== the absolute position the next
    token will be written at). Both are replaced wholesale by the outputs
    of the jitted prefill/decode functions; this object is the host-side
    holder that threads them from tick to tick.
    """

    def __init__(self, num_slots: int, num_layers: int, max_seq: int,
                 num_heads: int, head_dim: int, dtype="float32",
                 mesh=None, slot_axis: str = "model",
                 kv_dtype: Optional[str] = None):
        if num_slots < 1 or max_seq < 2:
            raise ValueError(
                f"need num_slots >= 1 and max_seq >= 2, got "
                f"{num_slots}/{max_seq}")
        if kv_dtype not in (None, "int8"):
            raise ValueError(
                f"kv_dtype must be None (dense) or 'int8', got "
                f"{kv_dtype!r}")
        self.num_slots = int(num_slots)
        self.num_layers = int(num_layers)
        self.max_seq = int(max_seq)
        self.num_heads = int(num_heads)
        self.head_dim = int(head_dim)
        self.dtype = jnp.dtype(dtype)
        self.kv_dtype = kv_dtype
        self.quantized = kv_dtype == "int8"
        self.mesh = mesh
        self.slot_axis = slot_axis
        shape = (self.num_slots, self.num_layers, self.max_seq,
                 self.num_heads, self.head_dim)
        if self.quantized:
            # {"q": int8 codes, "s": f32 per-(slot,layer,row) scales} —
            # halves KV memory (+1 scale per H*D row); the decode step
            # dequantizes in-register, so the codes never leave device
            def _zero_buf():
                return {"q": jnp.zeros(shape, jnp.int8),
                        "s": jnp.zeros(shape[:3], jnp.float32)}
        else:
            def _zero_buf():
                return jnp.zeros(shape, self.dtype)
        if mesh is not None:
            # GSPMD: shard the slot axis over the model axis of the mesh.
            # Slot rows are independent (attention never crosses slots),
            # so this partitioning is bitwise-identical to single-device
            # decode — each device owns whole slots, no reduction is split.
            from jax.sharding import NamedSharding, PartitionSpec
            axis_size = int(mesh.shape[slot_axis])
            if self.num_slots % axis_size:
                raise ValueError(
                    f"num_slots={self.num_slots} must divide evenly over "
                    f"mesh axis {slot_axis!r} (size {axis_size})")
            self._kv_sharding = NamedSharding(mesh,
                                              PartitionSpec(slot_axis))
            self._len_sharding = NamedSharding(mesh,
                                               PartitionSpec(slot_axis))
            sh = self._kv_sharding
            self.k = jax.tree_util.tree_map(
                lambda a: jax.device_put(a, sh), _zero_buf())
            self.v = jax.tree_util.tree_map(
                lambda a: jax.device_put(a, sh), _zero_buf())
            self.lengths = jax.device_put(
                jnp.zeros((self.num_slots,), jnp.int32),
                self._len_sharding)
        else:
            self._kv_sharding = None
            self._len_sharding = None
            self.k = _zero_buf()
            self.v = _zero_buf()
            self.lengths = jnp.zeros((self.num_slots,), jnp.int32)
        self._free: List[int] = list(range(self.num_slots))
        self._active: set = set()

    # -- slot lifecycle (host side) -----------------------------------------
    @property
    def free_slots(self) -> int:
        return len(self._free)

    @property
    def active_slots(self) -> Tuple[int, ...]:
        return tuple(sorted(self._active))

    def alloc(self) -> int:
        """Claim a free slot (lowest-index first, so short-lived tests are
        deterministic). The caller must prefill before decoding it."""
        if not self._free:
            raise SlotsExhausted(
                f"all {self.num_slots} KV slots are in use")
        slot = self._free.pop(0)
        self._active.add(slot)
        return slot

    def free(self, slot: int):
        """Return a slot to the pool. Stale K/V rows stay in the buffers —
        they are masked by the length vector and overwritten by the next
        occupant's prefill, so no device work is needed.

        Raises on an out-of-range slot and on a slot that is not active
        — a silent double-free would re-append the slot and hand it to
        two sequences at once (interleaved K/V corruption). The
        regression test pins both guards."""
        if not (0 <= slot < self.num_slots) or slot not in self._active:
            raise ValueError(
                f"slot {slot} is not active (double free?)")
        self._active.discard(slot)
        self._free.append(slot)
        self._free.sort()

    def reset(self):
        """Free every slot and zero the length vector (buffers are left as
        is — lengths gate validity). For tests and engine restarts."""
        self._free = list(range(self.num_slots))
        self._active.clear()
        lengths = jnp.zeros((self.num_slots,), jnp.int32)
        if self._len_sharding is not None:
            lengths = jax.device_put(lengths, self._len_sharding)
        self.lengths = lengths

    # -- functional state threading -----------------------------------------
    def swap(self, k, v, lengths):
        """Install the arrays returned by a jitted prefill/decode call.
        Shape-checked: a shape change would mean a recompile upstream."""
        def _shapes(buf):
            return [leaf.shape for leaf in jax.tree_util.tree_leaves(buf)]
        assert _shapes(k) == _shapes(self.k) \
            and _shapes(v) == _shapes(self.v), (_shapes(k), _shapes(self.k))
        self.k, self.v, self.lengths = k, v, lengths

    def kv_bytes(self) -> int:
        """Device bytes held by the K+V buffers (the slots-per-chip
        denominator the int8 acceptance bar is measured with)."""
        return kv_nbytes(self.k) + kv_nbytes(self.v)

    def host_lengths(self) -> np.ndarray:
        """One deliberate device->host fetch of the per-slot lengths (used
        by tests and ``/statsz``, never by the per-tick hot path — the
        scheduler tracks lengths on host from the tokens it already
        fetched)."""
        return np.asarray(jax.device_get(self.lengths))  # noqa: PTA002 -- deliberate observability fetch (tests, /statsz); the tick loop never calls this

    def host_slot_kv(self, slot: int, n: int) -> Tuple[np.ndarray, np.ndarray]:
        """One deliberate device->host copy of a slot's first ``n`` K/V
        rows as ``[num_layers, n, heads, head_dim]`` host arrays — the
        prefix-store export path. Called once per *admission* (after a
        prefill populated the rows), never on the per-tick path."""
        if self.quantized:
            raise NotImplementedError(
                "prefix export from an int8 KV cache is unsupported "
                "(prefix reuse is gated off at config time for "
                "kv_dtype='int8'; see LLMEngineConfig)")
        if not (0 <= slot < self.num_slots) or not (0 < n <= self.max_seq):
            raise ValueError(f"bad prefix export slot={slot} n={n}")
        k = np.asarray(jax.device_get(self.k[slot, :, :n]))  # noqa: PTA002 -- admission-time prefix-store export (one copy per admitted prompt); never on the per-tick path
        v = np.asarray(jax.device_get(self.v[slot, :, :n]))  # noqa: PTA002 -- admission-time prefix-store export; paired with the K fetch above
        return k, v

    def __repr__(self):
        return (f"StaticKVCache(slots={self.num_slots}, "
                f"layers={self.num_layers}, max_seq={self.max_seq}, "
                f"heads={self.num_heads}, head_dim={self.head_dim}, "
                f"active={len(self._active)})")


# -- functional update kernels (used inside jitted programs) ----------------

def append_tokens_kv(kb, vb, k_new, v_new, positions):
    """Write T new tokens' K/V for every slot starting at that slot's
    position (one layer's buffers — a step updates layer *l*'s cache
    before layer *l* attends, so the update is interleaved with the
    forward pass). ``T = 1`` is the decode tick, the speculative verify
    step lands its k+1 candidate rows with the same call.

    ``kb``/``vb``: ``[S, max_seq, H, D]`` (or the int8 dict);
    ``k_new``/``v_new``: ``[S, T, H, D]``; ``positions``: ``[S]`` int32.
    A vmapped ``lax.dynamic_update_slice`` over the slot axis — per-slot
    starts are traced values, so XLA lowers this to one scatter per leaf,
    keeping the step a single fused program.
    """
    if is_quantized_kv(kb):
        return (_append_tokens_kv_q(kb, k_new, positions),
                _append_tokens_kv_q(vb, v_new, positions))

    def _one(row_k, row_v, kn, vn, pos):
        # row_*: [max_seq, H, D]; kn/vn: [T, H, D]
        start = (pos, 0, 0)
        return (jax.lax.dynamic_update_slice(row_k, kn, start),
                jax.lax.dynamic_update_slice(row_v, vn, start))

    return jax.vmap(_one)(kb, vb, k_new, v_new, positions)


def _append_tokens_kv_q(buf, new, positions):
    """int8 variant: quantize the new rows (one scale per row) and land
    code + scale with the same vmapped ``dynamic_update_slice`` shape —
    still one scatter per leaf."""
    qs = quantize_kv_rows(new)                 # q [S, T, H, D], s [S, T]

    def _one(row_q, row_s, qn, sn, pos):
        # row_q: [max_seq, H, D] int8; row_s: [max_seq] f32
        return (jax.lax.dynamic_update_slice(row_q, qn, (pos, 0, 0)),
                jax.lax.dynamic_update_slice(row_s, sn, (pos,)))

    q, s = jax.vmap(_one)(buf["q"], buf["s"], qs["q"], qs["s"], positions)
    return {"q": q, "s": s}


def append_token_kv(kb, vb, k_new, v_new, positions):
    """:func:`append_tokens_kv` for one token per slot: ``k_new``/``v_new``
    ``[S, H, D]``."""
    return append_tokens_kv(kb, vb, k_new[:, None], v_new[:, None],
                            positions)


def write_prompt_kv_at(k_buf, v_buf, k_new, v_new, slot_ids, starts):
    """Write K/V rows into slots at per-request offsets — the
    prefix-reuse writer. ``k_new``/``v_new``: ``[B, L_layers, L, H, D]``;
    ``starts``: length-B offsets (0 == :func:`write_prompt_kv`). ONE
    batched ``dynamic_update_slice`` per request covers all layers at
    once — no per-layer host loop, the tentpole invariant for prefix
    bulk-copy."""
    if is_quantized_kv(k_buf):
        return (_write_prompt_kv_q(k_buf, k_new, slot_ids, starts),
                _write_prompt_kv_q(v_buf, v_new, slot_ids, starts))
    b = k_new.shape[0]
    for i in range(b):
        start = (slot_ids[i], 0, starts[i], 0, 0)
        k_buf = jax.lax.dynamic_update_slice(k_buf, k_new[i][None], start)
        v_buf = jax.lax.dynamic_update_slice(v_buf, v_new[i][None], start)
    return k_buf, v_buf


def _write_prompt_kv_q(buf, new, slot_ids, starts=None):
    """int8 variant of the prompt writers: quantize the ``[B, L, Lp, H,
    D]`` rows (one scale per row) and land codes + scales per request —
    still one ``dynamic_update_slice`` pair per request for all layers."""
    qs = quantize_kv_rows(new)           # q like new, s [B, L, Lp]
    q, s = buf["q"], buf["s"]
    b = new.shape[0]
    for i in range(b):
        st = 0 if starts is None else starts[i]
        q = jax.lax.dynamic_update_slice(
            q, qs["q"][i][None], (slot_ids[i], 0, st, 0, 0))
        s = jax.lax.dynamic_update_slice(
            s, qs["s"][i][None], (slot_ids[i], 0, st))
    return {"q": q, "s": s}


def write_prompt_kv(k_buf, v_buf, k_prompt, v_prompt, slot_ids):
    """Write whole-prompt K/V into the given slots at offset 0.

    ``k_prompt``/``v_prompt``: ``[B, L_layers, L_prompt, H, D]``;
    ``slot_ids``: length-B int sequence (static Python ints or traced
    scalars). B is static, so the loop unrolls into B
    ``dynamic_update_slice`` ops — prefill batches are small (usually 1
    per admission) and each op writes one contiguous slot row.
    """
    if is_quantized_kv(k_buf):
        return (_write_prompt_kv_q(k_buf, k_prompt, slot_ids),
                _write_prompt_kv_q(v_buf, v_prompt, slot_ids))
    b = k_prompt.shape[0]
    for i in range(b):
        start = (slot_ids[i], 0, 0, 0, 0)
        k_buf = jax.lax.dynamic_update_slice(k_buf, k_prompt[i][None], start)
        v_buf = jax.lax.dynamic_update_slice(v_buf, v_prompt[i][None], start)
    return k_buf, v_buf


def per_slot(z, trailing: int):
    """``[S, *rest]`` (one token per slot) or ``[S, T, *rest]`` as
    ``[S, T, *rest]``, ``rest`` being the last ``trailing`` axes."""
    return z.reshape((z.shape[0], -1) + z.shape[z.ndim - trailing:])


def valid_mask(positions, max_seq, dtype=jnp.float32):
    """Additive attention mask ``[S, 1, T, max_seq]`` for queries at
    ``positions`` ``[S, T]`` (or ``[S]``: one query per slot): 0 where the
    cache row index is <= the query's position (a just-written token
    attends to itself and the whole valid prefix), -1e9 beyond — the same
    finite -1e9 the dense path uses, so softmax zeros stale rows exactly
    (exp(-1e9) underflows to 0.0 in f32)."""
    idx = jnp.arange(max_seq, dtype=jnp.int32)[None, None]     # [1,1,max]
    ok = idx <= per_slot(positions, 0)[:, :, None]             # [S,T,max]
    return jnp.where(ok, 0.0, -1e9).astype(dtype)[:, None]


# -- cache views of models.gpt.gpt_block (used inside jitted programs) -------

class SlotRows:
    """The view of new tokens per slot over the slot buffers: layer ``li``
    writes its rows at ``positions`` and attends over the slot's whole row
    under the validity mask; int8 rows are dequantised here, the buffers
    stay quantised. ``positions`` ``[S]`` with ``q``/``k``/``v``
    ``[S, H, D]`` is the decode tick; ``[S, T]`` (consecutive from column
    0) with ``[S, T, H, D]`` the speculative verify step's ``k + 1``
    candidate rows. :meth:`buffers` hands the written buffers back when
    the layers are done."""

    def __init__(self, kbuf, vbuf, positions, dtype):
        self.kbuf, self.vbuf = kbuf, vbuf
        self.starts = per_slot(positions, 0)[:, 0]
        self.mask = valid_mask(positions, kv_max_seq(kbuf), dtype)
        self._k, self._v = [], []

    def attend(self, li, q, k, v, scale):
        kb, vb = append_tokens_kv(kv_layer_view(self.kbuf, li),
                                  kv_layer_view(self.vbuf, li),
                                  per_slot(k, 2), per_slot(v, 2),
                                  self.starts)
        self._k.append(kb)
        self._v.append(vb)
        return masked_attention(q, dequantize_kv(kb, q.dtype),
                                dequantize_kv(vb, q.dtype), self.mask, scale)

    def buffers(self):
        return kv_stack_layers(self._k), kv_stack_layers(self._v)


class TailRows:
    """The view of prompt tails behind cached prefixes: request ``i``'s
    queries attend over its slot's whole logical row with the fresh tail
    K/V spliced in at ``starts[i]``, under ``mask`` (offset-causal over
    the row). ``rows(buf, li)`` reads layer ``li``'s logical rows
    ``[B, max_seq, H, D]`` of the requests' slots: a slice of the slot
    buffers, or a gather through block tables. The buffers are not written
    here: ``kv`` records each layer's tail ``(k, v)`` and the program
    writes them once, after the layer loop. Dense rows only."""

    def __init__(self, rows, kbuf, vbuf, starts, mask):
        self.rows, self.kbuf, self.vbuf = rows, kbuf, vbuf
        self.starts, self.mask = starts, mask
        self.kv = []

    def attend(self, li, q, k, v, scale):
        self.kv.append((k, v))

        def _splice(row, new, st):
            return jax.lax.dynamic_update_slice(row, new, (st, 0, 0))

        row_k = jax.vmap(_splice)(self.rows(self.kbuf, li), k, self.starts)
        row_v = jax.vmap(_splice)(self.rows(self.vbuf, li), v, self.starts)
        return masked_attention(q, row_k, row_v, self.mask, scale)
