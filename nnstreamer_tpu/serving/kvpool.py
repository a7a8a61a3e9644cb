"""Paged KV-cache allocator: one preallocated device arena, block tables.

A contiguous cache would give every one of ``max_streams`` decode lanes
the full ``max_seq`` window — HBM cost B×S whether streams use it or
not, concurrency hard-capped at B. This module carves the bytes into
fixed ``block_tokens``-sized blocks instead (the compiler-first O(1)
autoregressive-caching form, PAPERS.md). It is the serving engine's one
KV store:

- **Arena** — one device pytree per codec, leaves ``[L, NTOT, parts, T,
  *entry]``: what a token's entry is the model family states
  (``models/family.py`` ``kv_entry``) — keys and values per head are two
  parts of ``[h, dh]`` (int8 adds a ``[L, NTOT, 2, T, h]`` scale leaf),
  a latent cache ONE part of ``[width]``, a row every head shares — and
  allocation, refcounts, sentinel, scatter and block copy are the same
  code for either. OR HEADS-MAJOR, ``[L, NTOT, parts, h, T, dh]`` (scale
  leaf ``[L, NTOT, 2, h, T]``), where the entry's heads are not whole
  tiles of 8 rows (``h % 8``: 2, 10): a head's ``T`` tokens are then whole
  tiles,
  where two rows of ``[T, 2, dh]`` to a tile made every flat view and
  every scatter a copy of the whole arena. Who decides: the codec that
  makes the arena (``models/transformer.py`` ``_kv_codec`` by
  ``kv_heads_major``, from ``kv_entry``'s shape alone); it states the
  order as ``codec.heads_major``, the pool repeats it as
  ``pool.heads_major`` and ``snapshot()["heads_major"]``, and nothing
  reads it off a shape (``[2, 16, dh]`` is both orders). The order is
  seen by the prefill's scatter (which transposes the PROMPT's rows),
  ``stream_rows`` (which hands out ``[.., tokens, h, dh]`` whatever the
  order) and the ``tp`` spec's head axis; the block copy, allocation and
  accounting do not care. The heads keep an axis of their own in either
  order (not flat ``[T * h, dh]`` rows) because ``tp`` shards it. ONE
  buffer per
  leaf that the decode program updates in place: it is a carry of the
  K-step scan and of the layer scan, never a scan's ``xs``/``ys``, and
  the layer is one more index beside the block (``[layer, block, :,
  slot]``; why: ``build_paged_decode_step``). ``NTOT = num_blocks + 1``:
  index ``num_blocks`` of every layer is a permanent ZERO block that is
  never allocated and never written.
- **Sentinel** — unallocated block-table entries hold ``SENTINEL =
  NTOT``, deliberately out of bounds: gathers clamp onto the zero block
  (reads are exact zeros, finite and masked anyway) and scatters use
  ``mode="drop"`` (writes vanish). One sentinel serves empty batch
  lanes, bucket padding, and not-yet-allocated tail blocks alike.
- **Free list / refcounts** — LIFO free list (hot blocks stay hot in
  whatever cache hierarchy sits under HBM), per-block refcounts so
  copy-on-write prefix sharing is a ``retain``; a block returns to the
  free list when its last owner releases it. Allocation is
  all-or-nothing: a stream that cannot get every block it asked for
  gets none, so the engine's shed ladder sees a clean failure.
- **Lane state** — a model family whose layers keep state other than
  keys and values (``models/family.py`` ``lane_state``: the recurrent
  state and convolution tail of a state-space layer) gets a second arena
  beside the blocks, ``[layers, lanes, ...]`` per leaf: one slot per
  decode lane, so a stream keeps its lane for life. ``self.arena`` is then
  ``{"kv": blocks, "state": {leaf: array}}``, ONE pytree donated into the
  same programs. A slot is claimed with ``alloc_lane``, overwritten whole
  by the prefill's hand-over (``scatter_prefill(..., lane=)``: that is
  the reset), updated in place by the decode program under the lane's
  own index, and given back with ``release_lane``. An empty lane's slot is
  masked inside the program: read as zeros, never written.
- **Window arena** — a family whose attention layers are of two kinds
  (``models/family.py`` ``kv_window``: beside the layers that see
  everything, layers that see the last ``window`` positions only) gets a
  SECOND block arena of the same leaf form for the window layers,
  ``[window layers, NTOT_w, parts, T, *entry]``, with a free list,
  refcounts, sentinel and zero block of its own (``pool.win``: ``alloc``,
  ``release``, ``SENTINEL`` as the pool's). ``self.arena`` is then
  ``{"kv": blocks, "win": window blocks}``, one pytree donated into the
  same programs; a family that keeps lane state as well (``models/
  sambay.py``) has all three, ``{"kv", "win", "state"}``, and ONE scatter
  hands a prefill's three kinds over (``_scatter_kinds_impl``). WHO
  DECIDES: the family says which layers have a window and how wide; the engine owns a second table a lane, indexed by a
  position's block like the first, and gives a lane's blocks back to
  ``pool.win`` once they lie wholly behind the window (an entry of the
  window table is then the window arena's sentinel again, and no program
  reads it); ``scatter_prefill`` hands ALL of a prompt's blocks to the
  full arena and to the window arena the table's blocks that are held
  (the caller holds the last ``window``-covering ones; the rest of the
  window table is sentinel and drops).
- **Accounting** — the arena registers its bytes with the PR-12 HBM
  accountant under the ``kvcache`` category at construction, so cache
  pressure shows up in ``nns_mem_used_bytes{category="kvcache"}`` and
  rides the same evict → shed → cpu ladder as weights and frames.

Model-side consumers (models/transformer.py paged builders) take the
arena whole, with a block table, and hand it to the codec's
``paged_write``/``paged_read``, which address it by ``(layer, block,
slot)`` under the sentinel rules above — inside the jitted program,
where donation makes the write in place. Host-side code never
subscripts the arena outside this file (lint rule NNS118): every
host-side mutation (prefill scatter, COW block copy) must go through
the pool so refcounts, donation, and the zero block's invariants stay
in one place.
"""

from __future__ import annotations

import threading
import weakref
from typing import List, Optional, Sequence

import numpy as np

from nnstreamer_tpu.tensors import memory as _memory


def _scatter_prefill_impl(arena, cache1, bids, heads_major=False):
    """Scatter a prefill's contiguous cache ([L, parts, b, S, ...] leaves,
    a row a prompt) into arena blocks ``bids`` ([b * S/T] int32: row 0's
    S/T entries, then row 1's; sentinel entries drop). Block i of a row
    receives its slots [i*T, (i+1)*T) — including any trailing
    bucket-pad garbage in the last data block, which stays masked until
    the owning stream overwrites it (the padded-prefill contract of
    ``build_prefill``). For a ``heads_major`` arena the prompt's rows are
    transposed on the way (the prompt's, never the arena's)."""
    import jax
    import jax.numpy as jnp

    def leaf(a, c):
        L, parts, b, S = c.shape[:4]
        T = a.shape[4 if heads_major else 3]
        u = c.reshape((L, parts, b * S // T, T) + c.shape[4:])
        if heads_major:                              # [L,MB,parts,h,T,.]
            u = jnp.transpose(u, (0, 2, 1, 4, 3) + tuple(range(5, u.ndim)))
        else:
            u = jnp.moveaxis(u, 2, 1)                    # [L,MB,parts,T,...]
        return a.at[:, bids].set(u.astype(a.dtype), mode="drop")

    with jax.named_scope("nns.kv_scatter"):
        return jax.tree.map(leaf, arena, cache1)


def _scatter_kinds_impl(arena, cache1, bids, bids_w, lanes,
                        heads_major=False):
    """The hand-over of a prefill whose family keeps more than the full
    layers' blocks, every kind in one program: keys and values into blocks
    as above; the window layers' rows into their own arena under their own
    table (``bids_w`` names the blocks of the window's last positions; the
    rest drop); row i of each state leaf ``[layers, b, ...]`` over the
    whole of slot ``lanes[i]`` (``[b]``; a lane past the arena's drops).
    ``bids_w`` / ``lanes`` are None for a kind the arena lacks."""
    import jax

    out = {"kv": _scatter_prefill_impl(arena["kv"], cache1["kv"], bids,
                                       heads_major)}
    if "win" in arena:
        out["win"] = _scatter_prefill_impl(arena["win"], cache1["win"],
                                           bids_w, heads_major)
    if "state" in arena:
        with jax.named_scope("nns.state_scatter"):
            out["state"] = jax.tree.map(
                lambda a, c: a.at[:, lanes].set(c.astype(a.dtype),
                                                mode="drop"),
                arena["state"], cache1["state"])
    return out


def _copy_block_impl(arena, src, dst):
    """Copy one physical block across every layer/leaf — the COW fault
    path when a stream extends a shared prefix whose tail block is only
    partially full."""
    import jax

    return jax.tree.map(lambda a: a.at[:, dst].set(a[:, src]), arena)


class _Blocks:
    """Host-side bookkeeping of ONE arena's blocks: a LIFO free list and
    refcounts under a lock. ``SENTINEL`` (``num_blocks + 1``, out of
    bounds) is the arena's table entry for "no block"; index
    ``num_blocks`` is its zero block."""

    def __init__(self, num_blocks: int, what: str = "BlockPool"):
        if num_blocks <= 0:
            raise ValueError(f"{what}: num_blocks must be positive, "
                             f"got {num_blocks}")
        self.what = what
        self.num_blocks = int(num_blocks)
        self.ntot = self.num_blocks + 1       # + the permanent zero block
        self.SENTINEL = self.ntot             # out of bounds on purpose
        self._lock = threading.Lock()
        self._free: List[int] = list(range(self.num_blocks))
        self._ref = np.zeros(self.num_blocks, np.int64)

    @property
    def free_blocks(self) -> int:
        with self._lock:
            return len(self._free)

    def alloc(self, k: int) -> Optional[List[int]]:
        """All-or-nothing: ``k`` fresh blocks (refcount 1 each) or None."""
        if k <= 0:
            return []
        with self._lock:
            if len(self._free) < k:
                return None
            ids = [self._free.pop() for _ in range(k)]
            for i in ids:
                self._ref[i] = 1
            return ids

    def retain(self, ids: Sequence[int]) -> None:
        with self._lock:
            for i in ids:
                if self._ref[i] <= 0:
                    raise RuntimeError(
                        f"{self.what}.retain: block {i} is not live")
                self._ref[i] += 1

    def release(self, ids: Sequence[int]) -> None:
        with self._lock:
            for i in ids:
                if self._ref[i] <= 0:
                    raise RuntimeError(
                        f"{self.what}.release: block {i} over-released")
                self._ref[i] -= 1
                if self._ref[i] == 0:
                    self._free.append(i)

    def live_blocks(self) -> int:
        with self._lock:
            return int(np.count_nonzero(self._ref))

    def reset(self) -> None:
        with self._lock:
            self._free = list(range(self.num_blocks))
            self._ref[:] = 0


class BlockPool(_Blocks):
    """Allocator + device arena for one engine's paged KV cache.

    Host-side state (free list, refcounts) is guarded by a lock so the
    engine thread and observers can touch it concurrently; device state
    (``self.arena``) is owned by the engine loop, which threads it
    through jitted programs with donation and writes the result back.
    """

    def __init__(self, cfg, num_blocks: int, block_tokens: int,
                 kv_codec: Optional[str] = None, mesh=None,
                 owner: str = "kvpool", lanes: int = 0,
                 window_blocks: Optional[int] = None):
        from nnstreamer_tpu.models.transformer import _kv_codec

        super().__init__(num_blocks)
        if block_tokens <= 0:
            raise ValueError(f"BlockPool: block_tokens must be positive, "
                             f"got {block_tokens}")
        self.cfg = cfg
        self.block_tokens = int(block_tokens)
        self.kv_codec = kv_codec
        self.mesh = mesh
        self.owner = owner
        self._codec = _kv_codec(cfg, kv_codec)
        #: the order of the rows inside a block, as the codec that makes
        #: the arena states it: ``[.., h, T, dh]`` (True) or ``[.., T,
        #: *entry]``
        self.heads_major = self._codec.heads_major
        #: what each lane holds beside its blocks (None: nothing), and
        #: which lanes' slots are claimed
        self._family = cfg.family
        self._lane_state = self._family.lane_state(cfg)
        #: the window layers' ``(layers, window)`` and their arena's own
        #: blocks (None: the family's attention layers are of one kind)
        self._window = self._family.kv_window(cfg)
        self.win: Optional[_Blocks] = None
        if self._window is not None:
            if mesh is not None or not window_blocks:
                raise ValueError(
                    "BlockPool: a window arena needs window_blocks > 0 and "
                    "does not go with a mesh")
            self.win = _Blocks(window_blocks, "BlockPool.win")
        self.lanes = int(lanes) if self._lane_state else 0
        if self._lane_state and self.lanes <= 0:
            raise ValueError("BlockPool: a model with lane state needs "
                             "lanes > 0")
        self._lane_live: set = set()
        #: the arena is a dict by kind (``"kv"`` and ``"win"``, ``"state"``
        #: or both) and not the full layers' leaves alone
        self._kinds = bool(self._lane_state or self.win)
        self.arena = self._make_arena()

        import jax
        self.nbytes = _memory.pytree_nbytes(self.arena)
        self.state_bytes = _memory.pytree_nbytes(self.arena["state"]) \
            if self._lane_state else 0
        self.window_bytes = _memory.pytree_nbytes(self.arena["win"]) \
            if self.win else 0
        self._jit_scatter = jax.jit(
            _scatter_kinds_impl if self._kinds else _scatter_prefill_impl,
            donate_argnums=(0,), static_argnames=("heads_major",))
        self._jit_copy = jax.jit(_copy_block_impl, donate_argnums=(0,))

        acct = _memory.ACTIVE
        if acct is not None:
            acct.register(self.nbytes, "kvcache")
            self._acct_finalizer = weakref.finalize(
                self, _unregister_arena, weakref.ref(acct), self.nbytes)
        else:
            self._acct_finalizer = None

    # -- arena construction -------------------------------------------

    def _make_arena(self):
        import jax.numpy as jnp

        layers, parts, entry = self._family.kv_entry(self.cfg)
        arena = self._codec.paged_init(layers, self.ntot, self.block_tokens,
                                       *entry, parts=parts)
        if self.mesh is not None:
            arena = self._place(arena)
        if not self._kinds:
            return arena
        arena = {"kv": arena}
        if self.win:
            arena["win"] = self._codec.paged_init(
                self._window[0], self.win.ntot, self.block_tokens, *entry,
                parts=parts)
        if self._lane_state:
            spec = dict(self._lane_state)
            n = spec.pop("layers")
            arena["state"] = {
                name: jnp.zeros((n, self.lanes) + tuple(shape), dtype)
                for name, (shape, dtype) in spec.items()}
        return arena

    def _place(self, arena):
        from jax.sharding import PartitionSpec as P

        from nnstreamer_tpu.parallel import serve as _serve

        names = set(self.mesh.axis_names)
        dp = "dp" if "dp" in names else None
        tp = "tp" if "tp" in names else None
        if dp and self.ntot % self.mesh.shape["dp"]:
            raise ValueError(
                f"BlockPool: arena block count {self.ntot} (incl. zero "
                f"block) must divide over dp={self.mesh.shape['dp']} — "
                f"pad num_blocks")

        def spec_of(leaf):
            # [L, NTOT, parts, T, h(, dh)], heads-major [L, NTOT, parts,
            # h, T(, dh)] — blocks over dp, heads over tp
            head = (None, dp, None, tp, None) if self.heads_major \
                else (None, dp, None, None, tp)
            return P(*(head + (None,) * (leaf.ndim - 5)))

        return _serve.place_tree(arena, self.mesh, spec_of,
                                 label=f"{self.owner}:kvpool")

    # -- host-side bookkeeping ----------------------------------------

    def alloc_lane(self) -> Optional[int]:
        """Claim the lowest free lane's state slot, or None when every
        lane is taken. The slot still holds its last owner's state until
        the new owner's prefill overwrites it."""
        with self._lock:
            free = set(range(self.lanes)) - self._lane_live
            if not free:
                return None
            self._lane_live.add(min(free))
            return min(free)

    def release_lane(self, lane: int) -> None:
        with self._lock:
            self._lane_live.discard(lane)

    def lane_state(self, lane: int) -> dict:
        """Host copies of what slot ``lane`` holds, leaf -> ``[layers,
        ...]``: for checks and tests. The caller sees to it that no
        program holds the arena meanwhile (an idle engine dispatches
        nothing)."""
        if not self._lane_state:
            return {}
        return {name: np.asarray(a[:, lane])
                for name, a in self.arena["state"].items()}

    def stream_rows(self, block_ids: Sequence[int], tokens: int,
                    window: bool = False):
        """Host copies of the first ``tokens`` entries that the blocks
        ``block_ids`` hold, in table order: per arena leaf ``[layers,
        parts, tokens, ...]``, a token's entry as the family states it
        (``[h, dh]``) whichever order the arena keeps a block's rows in.
        ``window``: the blocks are the window arena's.
        For checks and tests, as ``lane_state``:
        the caller sees to it that no program holds the arena meanwhile,
        and that no other stream was given the blocks since (a released
        block keeps its rows until its next owner writes them)."""
        import jax

        ids = np.asarray(block_ids, np.int32)
        kv = self.arena["win"] if window else self._kv(self.arena)

        def leaf(a):
            rows = np.asarray(a[:, ids])                 # [L,n,parts,T,...]
            if self.heads_major:
                rows = np.swapaxes(rows, 3, 4)           # from [..,h,T,...]
            rows = np.moveaxis(rows, 2, 1)
            rows = rows.reshape(rows.shape[:2] + (-1,) + rows.shape[4:])
            return rows[:, :, :tokens]

        return jax.tree.map(leaf, kv)

    # -- device-side helpers ------------------------------------------

    def _kv(self, tree):
        """The full layers' part of an arena or of a prefill's cache."""
        return tree["kv"] if self._kinds else tree

    def scatter_prefill(self, cache1, block_ids: Sequence[int],
                        lane: Optional[int] = None,
                        window_ids: Sequence[int] = (),
                        window_first: int = 0) -> None:
        """Move a one-prompt prefill cache into ``block_ids`` (padded with
        the sentinel up to S/T) and, for a family with lane state, the
        prefill's final state over slot ``lane``; for a family with window
        layers (it may be the same family), their rows of the prompt's blocks ``window_first ..
        window_first + len(window_ids) - 1`` into the window arena's
        ``window_ids`` (the blocks before them lie behind the window and
        are handed to nobody). Mutates ``self.arena`` in place (the old
        arena buffer is donated)."""
        self.scatter_rows(cache1, [(block_ids, lane, window_ids,
                                    window_first)])

    def scatter_rows(self, cache, rows: Sequence) -> None:
        """:meth:`scatter_prefill` for a prefill of ``len(rows)`` prompts,
        in ONE program: row i of ``cache`` goes where ``rows[i] =
        (block_ids, lane, window_ids, window_first)`` says or, for
        ``rows[i]`` None (a row that holds no prompt), nowhere: its block
        entries are the sentinel and its lane lies past the arena's, so it
        writes nothing."""
        import jax.numpy as jnp

        mb = _leaf_slots(self._kv(cache)) // self.block_tokens
        bids = np.full((len(rows), mb), self.SENTINEL, np.int32)
        bids_w = None if not self.win else np.full(
            (len(rows), mb), self.win.SENTINEL, np.int32)
        lanes = np.full(len(rows), self.lanes, np.int32)
        for i, row in enumerate(rows):
            if row is None:
                continue
            block_ids, lane, window_ids, window_first = row
            bids[i, :len(block_ids)] = block_ids
            if bids_w is not None:
                bids_w[i, window_first:window_first + len(window_ids)] = \
                    window_ids
            if lane is not None:
                lanes[i] = lane
        extra = ()
        if self._kinds:
            extra = (None if bids_w is None else jnp.asarray(bids_w.ravel()),
                     jnp.asarray(lanes) if self._lane_state else None)
        self.arena = self._jit_scatter(self.arena, cache,
                                       jnp.asarray(bids.ravel()), *extra,
                                       heads_major=self.heads_major)

    def copy_block(self, src: int, dst: int) -> None:
        """COW fault: duplicate physical block ``src`` into ``dst``."""
        import jax.numpy as jnp

        self.arena = self._jit_copy(self.arena,
                                    jnp.asarray(src, jnp.int32),
                                    jnp.asarray(dst, jnp.int32))

    def reset(self) -> None:
        """Drop every allocation and rebuild a zeroed arena — the engine
        recovery path. Accounting is unchanged: same bytes."""
        super().reset()
        with self._lock:
            self._lane_live.clear()
        if self.win:
            self.win.reset()
        self.arena = self._make_arena()

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "num_blocks": self.num_blocks,
                "block_tokens": self.block_tokens,
                "free_blocks": len(self._free),
                "live_blocks": int(np.count_nonzero(self._ref)),
                "nbytes": self.nbytes,
                "state_slots": self.lanes,
                "state_slots_live": len(self._lane_live),
                "state_bytes": self.state_bytes,
                "heads_major": int(self.heads_major),
                **({"window_blocks": self.win.num_blocks,
                    "window_blocks_live": self.win.live_blocks(),
                    "window_bytes": self.window_bytes} if self.win else {}),
            }


def _leaf_slots(cache1) -> int:
    """Sequence length S of a prefill's contiguous cache pytree."""
    import jax

    return jax.tree_util.tree_leaves(cache1)[0].shape[3]


def _unregister_arena(acct_ref, nbytes):
    acct = acct_ref()
    if acct is not None:
        acct.unregister(nbytes, "kvcache")
