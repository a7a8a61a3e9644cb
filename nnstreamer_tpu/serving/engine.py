"""Continuous-batching decode engine.

The TPU-first serving design, contrasted with the reference's query server
(one request = one pipeline invoke,
/root/reference/gst/nnstreamer/tensor_query/tensor_query_server.c):

- **One KV store.** Keys and values live in one preallocated arena of
  fixed-size blocks (``serving/kvpool.py``); a stream owns a block table
  into it. Admission is bounded by FREE BLOCKS, so more streams than
  decode lanes can be admitted and time-share the lanes, and a shared
  prompt prefix costs its blocks once.
- **One static program.** ``max_streams`` decode lanes step together.
  The hot loop is ONE jitted function over (arena, block tables) whose
  shapes never change — no recompiles as streams come and go. Empty
  lanes decode garbage that the host ignores; on a systolic array the
  wasted lanes cost nothing extra because the batched matmul runs anyway
  (utilization, not correctness, is what admission manages).
- **Multi-step dispatch.** Each dispatch runs ``steps_per_dispatch``
  decode steps under ``lax.scan`` and returns a ``[B, K]`` token block —
  per-call overhead (Python, the host↔device round trip) amortizes
  over K tokens. Streams hitting EOS mid-block waste at most K-1 slots of
  compute; the host truncates at the first EOS.
- **Bucketed prefill.** Prompts are right-padded to power-of-two buckets
  so prefill compiles once per bucket, not once per prompt length. Logits
  come from the true last position (``build_prefill`` lengths arg), and
  pad kv entries are provably unreachable (see models/transformer.py
  build_prefill docstring). A bucket's ONE program takes a fixed number
  of prompts, set by the model's shapes (``models/family.py
  prefill_rows``): where one prompt's tokens are too few to keep the
  chip's arithmetic busy while the weights stream in, the cold prompts
  admitted at one loop boundary share a program, and its one read of the
  weights.
- **Stream-local determinism.** Each stream's PRNG key is derived from
  (engine seed, stream id), so sampled output is reproducible regardless
  of which other streams share the batch — per-stream results never
  depend on batch composition (the decode math is row-independent).

Host-side state (positions, last tokens, keys, block tables) is a few
hundred int32s uploaded per dispatch; only the arena stays device-resident,
donated into every dispatch so XLA updates it in place.
"""

from __future__ import annotations

import collections
import itertools
import queue as _queue
import threading
import time as _time
from typing import Any, Dict, Iterator, List, Optional

import numpy as np

from nnstreamer_tpu.log import get_logger
from nnstreamer_tpu.obs import timeline as _timeline
from nnstreamer_tpu.obs.quantiles import P2Quantile

log = get_logger("serving")

#: what an iteration of the engine loop is made of; every instant of the
#: loop thread belongs to exactly one (``ContinuousBatchingEngine._phase``)
PHASES = ("admit", "first_token", "select", "dispatch", "emit", "idle",
          "other")
#: the phases in which the device can stand with nothing queued while the
#: engine has work (``ContinuousBatchingEngine._enqueue``): ``first_token``
#: is entered with a prefill queued, ``idle`` has no work
STARVED_PHASES = ("admit", "select", "dispatch", "emit", "other")
#: the stall note: an iteration longer than ``STALL_MIN_S`` and than
#: ``STALL_FACTOR`` x the running median is logged with its phases
STALL_MIN_S = 1.0
STALL_FACTOR = 5.0

#: engine name -> (what builds its decode dispatch, K, the shapes of the
#: program's arguments), of the newest few engines: what
#: ``decode_program_text`` compiles again. No array and no engine is held.
#: Module state, because the one reader (the benchmark's per-layer
#: metrics) runs after the engine is gone and was never handed it.
_DECODE_PROGRAMS: Dict[str, tuple] = {}
_DECODE_PROGRAMS_KEPT = 4


def _start_host_copies(*arrays) -> None:
    """Start the copy of each device array to the host, so that the
    blocking reads that follow wait once for all of them."""
    for a in arrays:
        start = getattr(a, "copy_to_host_async", None)
        if start is not None:
            start()


def decode_program_text(engine: Optional[str] = None) -> Optional[str]:
    """The optimized HLO text of an engine's K-step decode program (the
    newest engine's unless named), or None if there is none.

    The profiler's device plane names an operation by its instruction
    alone (libtpu 0.0.34); each instruction's line in this text carries
    ``op_name``, the path of ``jax.named_scope`` names it was traced
    under (``…/nns.decode/…/kv_gather/gather``), which is how device time
    is put under the scopes (``benchmark/scope_reduce.py``).

    The program the engine runs cannot be asked: JAX leaves such names
    out of the compile cache's key, so a program found in the cache
    brings the names of whichever commit compiled it first (on the chip,
    PR 25: the parent's, with no scope in them). So the program is built
    and compiled once more, from a new function (JAX also keeps compiled
    programs in memory, by function) and with the names in the key: the
    same optimized program, instruction for instruction, under this
    code's names. A compile, or a cache hit of its own, under a JAX
    option that is process-wide while it lasts: call it beside a trace,
    not in a serving path."""
    import jax

    if engine is None:
        engine = next(reversed(_DECODE_PROGRAMS), None)
    entry = _DECODE_PROGRAMS.get(engine)
    if entry is None:
        return None
    build, k, shapes = entry
    names_in_key = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, names_in_key)
    jax.config.update(names_in_key, True)
    try:
        return build(k).lower(*shapes).compile().as_text()
    finally:
        jax.config.update(names_in_key, before)


class GenerationStream:
    """Handle for one submitted prompt: iterate to receive token ids as
    they are generated; ``None``-terminated internally."""

    _DONE = object()

    def __init__(self, stream_id: int, prompt_len: int):
        self.stream_id = stream_id
        self.prompt_len = prompt_len
        self.tokens: List[int] = []  # generated so far (post-prompt)
        #: chosen-token log-probabilities (model's own fp32 log_softmax,
        #: independent of temperature/top-k draw shaping), parallel to
        #: ``tokens``
        self.logprobs: List[float] = []
        self.finished = False
        self.finish_reason: Optional[str] = None  # "eos"|"length"|...
        self.cancelled = False
        #: the request's life on ``time.monotonic()``, stamped by the
        #: engine: ``submit()``; the loop takes the request to admit it
        #: (the last attempt, if the pool deferred one); the first token
        #: is emitted; the stream finishes. ``stream_id`` is the
        #: identifier its spans share.
        self.submit_t: Optional[float] = None
        self.admit_t: Optional[float] = None
        self.first_t: Optional[float] = None
        self.finish_t: Optional[float] = None
        #: the decode lane the stream is pinned to for life, where the
        #: model family keeps state per lane (``BlockPool.lane_state``)
        self.lane: Optional[int] = None
        #: the blocks the stream held when it finished, in table order:
        #: ids only, released with the finish (``BlockPool.stream_rows``
        #: reads what they still hold, for a check on an idle engine)
        self.blocks: tuple = ()
        #: for a family with window layers: ``(first, ids)``, the window
        #: arena's blocks it held when it finished and the index of the
        #: first of them in its table (block ``first + i`` holds positions
        #: ``(first + i) * T ..``)
        self.window_blocks: tuple = (0, ())
        self._q: _queue.Queue = _queue.Queue()
        self._unsent: List[int] = []  # emitted, not yet in the queue

    def cancel(self) -> None:
        """Request cancellation (client gone, timeout, user abort): the
        engine frees this stream's blocks and lane at the next dispatch
        and finishes it with reason "cancelled". Pending (not yet
        admitted) streams are dropped without prefilling. Safe from any
        thread; idempotent; a no-op once finished."""
        self.cancelled = True

    def __iter__(self) -> Iterator[int]:
        while True:
            item = self._q.get()
            if item is self._DONE:
                return
            yield from item

    def result(self, timeout: Optional[float] = None) -> List[int]:
        """Block until the stream finishes; returns all generated ids."""
        out = []
        deadline = None
        if timeout is not None:
            import time

            deadline = time.monotonic() + timeout
        while True:
            import time

            t = None if deadline is None else max(0.0,
                                                  deadline - time.monotonic())
            try:
                item = self._q.get(timeout=t)
            except _queue.Empty:
                raise TimeoutError(
                    f"stream {self.stream_id}: no token within {timeout}s")
            if item is self._DONE:
                return out
            out.extend(item)

    # engine-side
    def _emit_block(self, toks: List[int], logprobs: List[float],
                    wake: bool = True):
        """One program's tokens for this stream, in order. They reach the
        queue as ONE item, so a reader blocked on it wakes once for the
        block: now, or with ``wake=False`` at the next ``_wake`` (the
        end mark's at the latest)."""
        if not self.tokens:
            self.first_t = _time.monotonic()
        self.tokens.extend(toks)
        self.logprobs.extend(logprobs)
        self._unsent.extend(toks)
        if wake:
            self._wake()

    def _wake(self):
        if self._unsent:
            self._q.put(self._unsent)
            self._unsent = []

    def _finish(self, reason: str):
        if self.finished:
            return  # idempotent: cancel/stop/EOS may race benignly
        self.finished = True
        self.finish_reason = reason
        if self.finish_t is None:  # the engine stamps before its book-keeping
            self.finish_t = _time.monotonic()
        self._wake()
        self._q.put(self._DONE)


class _PrefixTrie:
    """Token trie over the prefix-cache keys: longest-common-prefix lookup
    in O(prompt_len), independent of entry count (the linear scan it
    replaces was O(entries × prompt_len) per admission).

    Each node counts the entries in its subtree and keeps a representative
    one (``rep``), so a lookup never descends below the walk: every entry
    in the deepest walkable node's subtree shares exactly the walked
    tokens with the prompt, i.e. all tie at the maximal LCP.
    """

    __slots__ = ("root",)

    @staticmethod
    def _node():
        return {"kids": {}, "entry": None, "count": 0, "rep": None}

    def __init__(self):
        self.root = self._node()

    def insert(self, key: tuple) -> None:
        node = self.root
        node["count"] += 1
        node["rep"] = key
        for tok in key:
            node = node["kids"].setdefault(tok, self._node())
            node["count"] += 1
            node["rep"] = key
        node["entry"] = key

    def remove(self, key: tuple) -> None:
        path = [self.root]
        node = self.root
        for tok in key:
            node = node["kids"][tok]
            path.append(node)
        node["entry"] = None
        for n in path:
            n["count"] -= 1
        # prune empty nodes; repair representatives that pointed at key
        for i in range(len(path) - 1, 0, -1):
            parent, child = path[i - 1], path[i]
            if child["count"] == 0:
                del parent["kids"][key[i - 1]]
        for n in path:
            if n["count"] > 0 and n["rep"] == key:
                n["rep"] = self._any_entry(n)

    @staticmethod
    def _any_entry(node):
        while node["entry"] is None:
            node = next(k for k in node["kids"].values() if k["count"] > 0)
        return node["entry"]

    def lookup(self, prompt) -> tuple:
        """→ (best_key, lcp): a cached key maximizing LCP with ``prompt``
        (an exact whole-prompt entry preferred), or (None, 0)."""
        node = self.root
        d = 0
        for tok in prompt:
            child = node["kids"].get(int(tok))
            if child is None:
                break
            node = child
            d += 1
        if d == 0 or node["count"] == 0:
            return None, 0
        if d == len(prompt) and node["entry"] is not None:
            return node["entry"], d  # exact match carries reusable logits
        return node["rep"], d


class _PendingRequest:
    def __init__(self, prompt: np.ndarray, max_new: int,
                 stream: GenerationStream):
        self.prompt = prompt
        self.max_new = max_new
        self.stream = stream

    def who(self) -> Dict[str, int]:
        """What a span caused by this request carries in its args."""
        return {"stream": self.stream.stream_id,
                "prompt": int(self.prompt.size)}


class _ColdAdmission:
    """A cold admission whose blocks (and lane, window blocks) are held and
    whose prefill is not yet enqueued: it waits for the other prompts of
    its loop boundary, to share their prefill program."""

    __slots__ = ("req", "blocks", "wblocks", "wfirst", "lane", "bucket")

    def __init__(self, req, blocks, wblocks, wfirst, lane, bucket):
        self.req, self.blocks, self.lane = req, blocks, lane
        self.wblocks, self.wfirst, self.bucket = wblocks, wfirst, bucket

    def scatter_row(self, block_tokens: int) -> tuple:
        """Where its prefill's row goes (``BlockPool.scatter_rows``): the
        blocks that hold the prompt, its lane, and the window blocks that
        hold the prompt's positions from ``wfirst``'s block on."""
        held = -(-self.req.prompt.size // block_tokens)
        return (self.blocks[:held], self.lane,
                self.wblocks[:held - self.wfirst], self.wfirst)

    def release(self, pool) -> None:
        pool.release(self.blocks)
        if self.wblocks:
            pool.win.release(self.wblocks)
        if self.lane is not None:
            pool.release_lane(self.lane)


class ContinuousBatchingEngine:
    """Batched multi-stream generation over one transformer model.

    Keys and values live in ONE place, the block arena
    (``serving/kvpool.py``): fixed-size blocks over one preallocated
    buffer, a block table per stream, admission bounded by FREE BLOCKS.
    Every admitted stream owns blocks; at each dispatch the ``max_streams``
    most urgent ones (per-token EDF deadlines) are bound to the decode
    lanes, so more streams than lanes time-share them, and a shared
    prompt prefix costs its blocks once (copy-on-write block tables).

    Parameters
    ----------
    cfg, params: a model config + param pytree. The config's ``family``
        (``models/family.py``) gives the prefill and paged-decode
        builders, and says what the arena holds for a token
        (``kv_entry``: its shape also decides the order of the rows
        inside a block, ``serving/kvpool.py``), what a decode lane
        holds beside its blocks and
        which options its programs bring: ``models.transformer`` is the
        dense member, ``models.hybrid`` the one with recurrent layers,
        ``models.mla`` the one whose cache is one latent row a token,
        ``models.afmoe`` the one with window layers beside full ones
        (``kv_window``): the window layers' keys and values live in a
        second arena under a second table a lane, and after every
        dispatch the engine gives back the blocks of that table that lie
        wholly behind the window (blocks given back, not a ring addressed
        in place: a block that goes back is any stream's next block, a
        lane that finishes early leaves nothing reserved, and a block
        another stream shares can be let go of where a ring would have to
        copy it first; the table keeps the full table's indexing, so one
        kernel reads both), ``models.sambay`` the one that states a window
        AND lane state: a stream takes a lane, full blocks and window
        blocks at admission and gives all three back at its end, and its
        cross layers read the one full layer's blocks, so ``kv_entry``
        says one layer and nothing here knows of the others.
        With a family that has lane state every stream keeps its lane
        for life. Of ``prefix_cache``, ``speculate``, ``prefill_chunk``,
        ``kv_quant`` and ``mesh=`` the dense family brings all; what a
        family does not bring (``ModelFamily.brings``) is refused at
        construction by name, with the family's own reason.
        The engine does not keep the tree it is given. At construction
        it makes, once, the tree its programs read
        (``models.family.serving_params``): a leaf the family's programs
        read as ``leaf.astype(cfg.dtype)`` and that arrives wider
        (float32 weights under a bfloat16 config) is rounded to
        ``cfg.dtype`` in one jitted call, every other leaf is held as the
        same array; ``mesh=`` placement and a speculative draft take the
        held tree. The rounding is the one every program would otherwise
        repeat at every call, so outputs are bit-equal; a caller that
        needs the float32 bytes back drops its own reference after
        construction. ``engine.params`` is the held tree;
        ``engine.weights`` records ``weight_bytes_given``,
        ``weight_bytes_held`` and ``weight_leaves_narrowed`` (logged at
        construction, exported as ``nns_serving_weight_*`` gauges, the
        held bytes registered with the HBM accountant when one is
        active).
    max_streams: decode lanes (B). Static — sizes the programs and, by
        default, the arena.
    max_seq: a stream's longest context S (defaults to ``cfg.max_seq``).
    steps_per_dispatch: decode steps fused into one device dispatch (K),
        or "auto" — start() measures the per-dispatch sync round trip
        and per-step decode time and picks K so the fixed dispatch cost
        amortizes to ≤~20% of a block (small on PCIe, large over a
        high-RTT link; see _calibrate_k).
    temperature / top_k / min_p: sampling config (``temperature<=0`` →
        greedy; see ``models.transformer.make_sampler``).
    eos_id: generation stops when the model emits this id (None → length
        -bounded only).
    seed: engine PRNG seed; per-stream keys fold in the stream id.
    min_bucket: smallest prefill padding bucket.
    mesh: optional ``jax.sharding.Mesh`` — multi-chip serving. Params
        shard per ``parallel.sharded.transformer_param_specs`` (heads/ffn
        over ``tp``), the arena shards its blocks over ``dp`` and its
        heads over ``tp``, and GSPMD propagates through the unchanged
        decode/prefill programs ("computation follows data") — batched
        decode collectives ride ICI, never the host. Requires
        ``max_streams % dp == 0`` and ``n_heads % tp == 0``.
    prefill_chunk: when set, prompts ingest in fixed chunks of this many
        tokens, ONE chunk per engine-loop iteration, interleaved with
        decode dispatches — admitting a long prompt then adds at most
        one chunk's latency per block to running streams instead of a
        whole-prompt stall (and prefill compiles exactly once, at shape
        ``[1, chunk]``, instead of once per length bucket). Padded tail
        positions are unreachable-before-overwrite exactly like bucket
        padding. Requires ``prompt length <= max_seq - prefill_chunk``.
    kv_quant: ``"int8"`` stores the arena quantized (per-vector absmax
        scales) — ~2× blocks per HBM byte, at a small, bounded numeric
        cost (models/transformer._Int8KVCodec).
    prefix_cache: keep the blocks of the last N admitted prompts
        (a reference on each; LRU, and evicted first when the pool runs
        out) and, when a new prompt shares a prefix with a cached one,
        share the whole blocks of that prefix and prefill only the
        remainder — the multi-turn/system-prompt reuse pattern. Exact by
        construction: the reused kv is the same physical blocks. Reuse
        is at BLOCK granularity (an exact repeat reuses the whole
        prompt), and chunked ingestion (``prefill_chunk``) stores
        entries but does not reuse them. 0 (default) disables.
    attention: "auto" (default) or "reference". "auto" runs the Pallas
        flash kernel (ops/flash_attention.py) for the O(s²) prompt pass
        on a TPU when the shapes tile (seq divisible by the block,
        head_dim ≤ 256), so long prompts stop materializing [s,s] score
        tiles in HBM, and the paged decode step through
        ``ops.paged_attention``: on a TPU, for a raw (not int8) arena
        and shapes its kernel takes, each lane's LIVE blocks are read
        where they lie in the arena (``nns_paged_decode`` in a trace),
        work that follows the tokens held; elsewhere the XLA form that
        gathers every lane's whole block table and attends under a mask
        (`_attend_cache`). ``decode_attention`` says which was built:
        "paged_kernel" or "gather". "reference" forces XLA attention
        everywhere. A ``mesh=`` engine keeps XLA attention (a
        ``pallas_call`` carries no partitioning rule), as do chunked
        ingestion, prefix extension and speculative verification
        (`build_paged_chunk`), whose attention is over several query
        positions a lane.
    block_tokens: tokens per block of the arena: a size, not a mode. A
        positive divisor of ``max_seq`` (``ValueError`` otherwise).
    kv_blocks: arena size in blocks. Defaults to ``max_streams * max_seq
        / block_tokens``: every lane's full context at once.
    kv_window_blocks: the window arena's size in blocks, for a family
        with window layers. Defaults to ``max_streams * (ceil((window +
        steps_per_dispatch) / block_tokens) + 1)``: the most every lane
        can hold at once.
    speculate: > 0 enables speculative decoding — a ``speculate_layers``
        -layer draft sliced from the target params
        (models/speculative.py) proposes K tokens per round inside the
        batched decode; the target verifies them in ONE chunk pass.
        Greedy only (temperature must be 0), single-chip only, and
        concurrency is capped at ``max_streams`` (the draft keeps a
        contiguous cache, one slot per lane). Output is byte-identical
        to non-speculative greedy decoding by construction.
    """

    def __init__(self, cfg, params, max_streams: int = 4,
                 max_seq: Optional[int] = None,
                 steps_per_dispatch: int = 8,
                 temperature: float = 0.0, top_k: int = 0,
                 min_p: float = 0.0,
                 eos_id: Optional[int] = None, seed: int = 0,
                 min_bucket: int = 16, mesh=None,
                 prefill_chunk: Optional[int] = None,
                 kv_quant: Optional[str] = None,
                 prefix_cache: int = 0,
                 attention: str = "auto",
                 slo_budget_ms: float = 0.0,
                 block_tokens: int = 16,
                 kv_blocks: Optional[int] = None,
                 kv_window_blocks: Optional[int] = None,
                 speculate: int = 0,
                 speculate_layers: Optional[int] = None):
        import jax
        import jax.numpy as jnp

        family = cfg.family
        #: the family keeps state per decode lane: a stream is pinned to
        #: its lane
        self._lane_state = family.lane_state(cfg) is not None
        # what a family can do comes from its record: an option its
        # programs do not bring is refused by name
        refused = [name for name, on in (
            ("prefix_cache", prefix_cache), ("speculate", speculate),
            ("prefill_chunk", prefill_chunk), ("kv_quant", kv_quant),
            ("mesh", mesh is not None)) if on and name not in family.brings]
        if refused:
            raise ValueError(
                f"serving: the {family.name} model family "
                f"{family.refusal}; it does not yet support "
                f"{', '.join(refused)}")
        self.cfg = cfg
        from nnstreamer_tpu.models.family import serving_params

        #: the tree every program reads, and what making it did
        #: (``serving_params``; the class docstring, "cfg, params")
        self.params, self.weights = serving_params(cfg, params)
        del params  # not kept: nothing below may read the given tree
        from nnstreamer_tpu.models.family import balance_tokens

        #: T_w, the prompt tokens whose matmuls take the chip as long as one
        #: read of the held weights (``_prefill_rows``). 0 where a cold
        #: prompt always has a program of its own: a ``mesh=`` engine (its
        #: programs are sharded, its weights read a shard a chip) and the
        #: options whose admissions bind a prompt's own results at once
        #: (a prefix entry keeps its logits and a later prompt of the same
        #: boundary may read its blocks; a chunk is one prompt's)
        self._balance = 0.0 if (mesh is not None or prefix_cache
                                or prefill_chunk is not None) \
            else balance_tokens(cfg, self.params,
                                self.weights["weight_bytes_held"])
        self.B = int(max_streams)
        self.S = int(max_seq or cfg.max_seq)
        self.block_tokens = int(block_tokens or 0)
        if self.block_tokens <= 0 or self.S % self.block_tokens:
            raise ValueError(
                f"serving: block_tokens must be a positive divisor of "
                f"max_seq ({self.S}), got {block_tokens}")
        #: steps_per_dispatch="auto": start() measures the per-dispatch
        #: sync round trip and the per-step decode time, then picks K so
        #: the fixed dispatch cost amortizes (see _calibrate_k) — on a
        #: PCIe-attached chip that lands small, on a high-RTT link large
        self._auto_k = steps_per_dispatch == "auto"
        self.K = 8 if self._auto_k else int(steps_per_dispatch)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.min_p = float(min_p)
        self.eos_id = eos_id
        self.seed = int(seed)
        self.min_bucket = int(min_bucket)

        self.prefill_chunk = None if prefill_chunk is None \
            else int(prefill_chunk)
        if self.prefill_chunk is not None and not (
                0 < self.prefill_chunk < self.S):
            raise ValueError(
                f"serving: prefill_chunk must be in (0, {self.S}), got "
                f"{prefill_chunk}")
        self.kv_quant = kv_quant
        if attention not in ("auto", "reference"):
            raise ValueError(
                f"serving: attention must be 'auto' or 'reference', got "
                f"{attention!r}")
        attention_fn = None
        if attention == "auto" and mesh is None:
            # single-chip only: pallas_call does not carry GSPMD
            # partitioning rules, so the meshed engine keeps XLA
            # attention (which GSPMD shards like the rest of prefill)
            from nnstreamer_tpu.ops import flash_attention

            attention_fn = flash_attention  # causal=True is its default
        self._prefill_fn = family.build_prefill(
            cfg, self.S, attention_fn=attention_fn, kv_codec=kv_quant)
        #: block-table width: blocks per stream at full context
        self.MB = self.S // self.block_tokens
        #: the family's window layers, ``(layers, window)``, or None; and
        #: the most blocks of the window arena a lane holds at a dispatch
        self._window = family.kv_window(cfg)
        if self._window is not None:
            if self._auto_k:
                raise ValueError("serving: a family with window layers "
                                 "sizes its window arena by a fixed "
                                 "steps_per_dispatch, not \"auto\"")
            self.MBW = min(self.MB, -(-(self._window[1] + self.K)
                                      // self.block_tokens) + 1)
        paged_attention_fn = None
        if attention == "auto" and mesh is None and kv_quant is None:
            # one chip, one raw arena leaf: as for prefill, a
            # pallas_call carries no partitioning rule
            from nnstreamer_tpu.ops import paged_attention

            paged_attention_fn = paged_attention
        self._paged_decode = family.build_paged_decode_step(
            cfg, self.block_tokens, self.S, kv_codec=kv_quant,
            paged_attention_fn=paged_attention_fn)
        # chunked ingestion (a batch-1 contiguous cache, scattered into
        # blocks by its last chunk), prefix extension and speculative
        # verification: a family's own builders, where it brings them
        self._chunk_fn = self._paged_chunk_fn = None
        if family.build_chunk_decode is not None:
            self._chunk_fn = family.build_chunk_decode(
                cfg, self.S, kv_codec=kv_quant)
            self._paged_chunk_fn = family.build_paged_chunk(
                cfg, self.block_tokens, self.S, kv_codec=kv_quant)
        #: in-progress chunked admission: (request, cache1, k) with
        #: k = next chunk index; one at a time, advanced between dispatches
        self._partial = None

        from nnstreamer_tpu.parallel import serve as _serve

        if mesh is None:
            # the held bytes, with the budget accountant when one is
            # active, for as long as the engine lives
            _serve.account_placement(self.params, "engine:lm", owner=self)
        else:
            from jax.sharding import PartitionSpec as P

            from nnstreamer_tpu.parallel.sharded import (
                transformer_param_specs,
            )

            for name, dim, total in (("dp", "max_streams", self.B),
                                     ("tp", "n_heads", cfg.n_heads)):
                if name in mesh.axis_names and total % mesh.shape[name]:
                    raise ValueError(
                        f"serving: {dim} ({total}) must divide by mesh "
                        f"axis {name!r} ({mesh.shape[name]})")

            def prune(spec):
                # drop axis names the mesh doesn't have (e.g. a dp-only
                # serving mesh has no "tp"; a dense model's mesh no "ep")
                # — absent axis = replicated on that dimension
                return P(*(a if (a is not None and a in mesh.axis_names)
                           else None for a in spec))

            specs = {k: prune(s)
                     for k, s in transformer_param_specs(cfg).items()}
            # serving-plane placement (parallel/serve.py): per-shard HBM
            # registers with the budget accountant when one is active
            self.params = _serve.place_params(self.params, mesh, specs,
                                              label="engine:lm")
        self._pending: "_queue.Queue[_PendingRequest]" = _queue.Queue()
        self._next_id = 0
        self._lock = threading.Lock()
        self._wake = threading.Event()
        self._stop_evt = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.stats: Dict[str, Any] = {
            "tokens_generated": 0, "dispatches": 0, "prefills": 0,
            # hand-overs of a program's tokens to a stream (``_hand_over``):
            # one a stream a dispatch, one a first token
            "emit_blocks": 0,
            "prefill_chunks": 0, "slot_steps": 0, "active_slot_steps": 0,
            "prefix_hits": 0, "prefix_tokens_reused": 0,
            "concurrent_streams_max": 0, "kv_sheds": 0, "kv_defers": 0,
            "spec_drafted": 0, "spec_accepted": 0,
            # where the loop thread's time went, integer microseconds
            # (``_phase``): the phases tile the loop, so they sum to
            # ``loop_us``. Every key exists from here on and stays an
            # ``int``: readers copy this dict from other threads
            "loop_us": 0, **{f"phase_{name}_us": 0 for name in PHASES},
            # the part of each closed phase in which the loop thread had
            # nothing queued on the device (``_enqueue``); the five sum
            # to ``starved_us``
            "starved_us": 0,
            **{f"starved_{name}_us": 0 for name in STARVED_PHASES},
            # requests that reached their first token, and for them the
            # sums of submit -> admit and admit -> first token
            "admissions": 0, "admit_wait_us": 0, "first_token_us": 0,
            "stalls": 0,
            # prompt tokens the prefill, prefix-extension and chunk programs
            # were given, the rows they computed (the bucket, the chunk;
            # every row of a cold prefill, rows that hold no prompt too),
            # and the loop iterations that admitted at least one request
            "prefill_tokens": 0, "prefill_bucket_tokens": 0,
            "admit_boundaries": 0,
            # the cold admissions' prefill programs: ``admissions`` less
            # this is the prompts that rode in another's program
            "prefill_programs": 0,
            # blocks the paged decode steps had to read (every lane's
            # live blocks, step by step) and blocks their tables name
            "kv_blocks_live": 0, "kv_blocks_table": 0,
            # a family with window layers: blocks a window layer's
            # attention had to read (every lane, step by step, as
            # ``kv_blocks_live`` counts a full layer's) and blocks given
            # back behind a window
            **({"kv_window_blocks_live": 0, "kv_window_blocks_released": 0}
               if self._window is not None else {}),
            # what the family's decode step counts of itself, summed over
            # the steps of every dispatch, and what its prefill says it
            # computed of each bucket's rows
            **{name: 0 for name in family.counters},
            **(family.prefill_counters(cfg, 0)
               if family.prefill_counters else {}),
        }
        self._counters = tuple(family.counters)
        self._prefill_counters = family.prefill_counters
        #: the engine's own after-the-fact record of what its loop did:
        #: one span per closed phase, one async span per request. Used
        #: while no process-wide timeline is installed (``_ledger``).
        self.ledger = _timeline.Timeline(4096)
        self._phase_t = _time.monotonic()   # where the open phase began
        self._iter_us: Dict[str, int] = {}  # this iteration, by phase
        self._iter_starved: Dict[str, int] = {}  # of which starved
        #: what the loop thread knows of the device's queue (``_enqueue``):
        #: programs it has enqueued, the newest of them whose result a
        #: blocking fetch has returned, and the instant of the open phase
        #: from which something has been queued (its start, if something
        #: was queued then; None while nothing is)
        self._dev_enq = self._dev_seen = 0
        self._dev_busy_t: Optional[float] = None
        self._iter_median = P2Quantile(0.5)
        from nnstreamer_tpu.obs import (
            get_registry,
            register_engine_collector,
        )

        #: registry label distinguishing concurrent engines in one process
        self.obs_name = f"engine{next(self._OBS_SEQ)}"
        self._m_queue_wait = get_registry().histogram(
            "nns_serving_queue_wait_seconds",
            "submit() to batch-slot admission wait",
            engine=self.obs_name)
        register_engine_collector(self)
        log.info("serving: %s holds %d B of weights (given %d B, %d leaves "
                 "narrowed to %s)", self.obs_name,
                 self.weights["weight_bytes_held"],
                 self.weights["weight_bytes_given"],
                 self.weights["weight_leaves_narrowed"],
                 jnp.dtype(cfg.dtype).name)
        #: request-path SLO admission (serving/scheduler.py): submit()
        #: rejects prompts whose deadline is unmeetable under the EWMA
        #: per-request service estimate; 0 = admit everything (default)
        self._slo = None
        if float(slo_budget_ms or 0.0) > 0:
            from nnstreamer_tpu.serving.scheduler import SloScheduler

            self._slo = SloScheduler(budget_ms=float(slo_budget_ms),
                                     name=self.obs_name)
        from nnstreamer_tpu.obs.flight import LMTokenStats

        #: per-token latency quantiles (TTFT vs inter-token split) —
        #: nns_lm_ttft_p50/p99_ms, nns_lm_token_p50/p99_ms
        self._lm_stats = LMTokenStats(self.obs_name)
        self._mesh = mesh
        from nnstreamer_tpu.serving import kvpool as _kvpool

        nb = int(kv_blocks) if kv_blocks else self.B * self.MB
        if mesh is not None and "dp" in mesh.axis_names:
            # arena block axis shards over dp: pad so NTOT divides
            nb += (-(nb + 1)) % mesh.shape["dp"]
        #: the one place keys and values live: the block arena
        self._pool = _kvpool.BlockPool(
            cfg, nb, self.block_tokens,
            kv_codec=kv_quant, mesh=mesh, owner=self.obs_name,
            lanes=self.B, window_blocks=None if self._window is None
            else int(kv_window_blocks or self.B * self.MBW))
        #: sid → per-stream decode state (stream, blocks, pos, last,
        #: key, budget, deadline_t, slot); engine thread only. Every
        #: ADMITTED stream lives here whether or not it currently
        #: holds one of the B decode lanes.
        self._sstate: Dict[int, dict] = {}
        #: streams that go on whose block the last hand-over held back:
        #: put once the next dispatch is queued (``_wake_streams``)
        self._unwoken: List[GenerationStream] = []
        #: admission head deferred on block exhaustion (FIFO order
        #: is preserved: nothing behind it admits until it fits)
        self._held: Optional[_PendingRequest] = None
        #: decode lane → sid occupying it (None = free lane)
        self._lane: List[Optional[int]] = [None] * self.B
        #: host mirror of the device block tables, one row per lane
        self._bt = np.full((self.B, self.MB), self._pool.SENTINEL,
                           np.int32)
        #: the same for the window arena: entry ``j`` is the block that
        #: holds positions ``j * T ..`` of the window layers, or that
        #: arena's sentinel (not yet allocated, or given back)
        self._bt_w = None if self._window is None else np.full(
            (self.B, self.MB), self._pool.win.SENTINEL, np.int32)
        #: the form the decode program attends in: "paged_kernel"
        #: (ops/paged_attention.py: live blocks read in place) or "gather"
        self.decode_attention = "gather"
        if paged_attention_fn is not None:
            from nnstreamer_tpu.ops.paged_attention import (
                paged_attention_form,
            )

            kv = self._pool._kv(self._pool.arena)
            self.decode_attention = paged_attention_form(
                jax.ShapeDtypeStruct(
                    (self.B, 1, cfg.n_heads, kv.shape[-1]), kv.dtype),
                kv, self._bt, v_width=family.latent_value_width(cfg),
                heads_major=self._pool.heads_major,
                window=self._window and self._window[1])
        #: bytes of the arena's entry for one token, all layers (of a
        #: family with window layers: the full ones), as held; for a
        #: latent arena or two arenas the form beside it (a dense engine's
        #: stats stay integers: ``tests/test_lm_tracing.py``)
        self.stats["kv_bytes_per_token"] = (
            self._pool.nbytes - self._pool.state_bytes
            - self._pool.window_bytes) \
            // (self._pool.ntot * self.block_tokens)
        if self._window is not None:
            self.stats["kv_window_bytes_per_token"] = \
                self._pool.window_bytes \
                // (self._pool.win.ntot * self.block_tokens)
        if family.latent_value_width(cfg) is not None \
                or self._window is not None:
            self.stats["decode_attention"] = self.decode_attention
        #: the form the decode program runs its routed experts in:
        #: "grouped_kernel" (ops/grouped_matmul.py: a Pallas grid over the
        #: sorted tiles) or "tile_loop"; None for a family without experts
        self.expert_matmul = None
        if family.expert_matmul is not None:
            self.expert_matmul = family.expert_matmul(cfg, self.B)
            self.stats["expert_matmul"] = self.expert_matmul
            log.info("serving: %s runs its routed experts as %s",
                     self.obs_name, self.expert_matmul)
        #: the form the decode program updates its lanes' recurrent state
        #: in: "lane_kernel" (ops/lane_state.py: one in-place Pallas pass
        #: over the state arena a layer) or "reference"; None for a family
        #: without lane state
        self.state_update = None
        if family.state_update is not None:
            self.state_update = family.state_update(cfg, self.B)
            self.stats["state_update"] = self.state_update
            log.info("serving: %s updates its lane state as %s",
                     self.obs_name, self.state_update)
        self.prefix_cache = int(prefix_cache)
        if self.prefix_cache < 0:
            raise ValueError(
                f"serving: prefix_cache must be >= 0, got {prefix_cache}")
        #: tuple(prompt ids) → (block ids, logits[1,V]): the entry holds
        #: a reference on each block (arena bytes the pool registered
        #: once) — LRU, engine-thread only; the trie mirrors the key set
        #: for O(prompt_len) longest-common-prefix admission lookups
        self._prefix: "collections.OrderedDict" = collections.OrderedDict()
        self._prefix_trie = _PrefixTrie()
        from nnstreamer_tpu.utils.stats import InvokeStats

        #: reference-style windowed read-outs (latency_us = one [B,K]
        #: dispatch wall time incl. the token fetch; throughput_milli =
        #: dispatches/s ×1000) — the SAME instrument every pipeline
        #: element exposes (utils/stats.py), so engine and element
        #: metrics read uniformly
        self.invoke_stats = InvokeStats()

        from nnstreamer_tpu.models.transformer import make_sampler

        # the ONE sampling function (shared with the repo-loop sampled
        # step) — seeds the first token and every dispatch-loop draw with
        # identical math, per-row keys keeping streams batch-independent
        sample = make_sampler(cfg.vocab, self.temperature, self.top_k,
                              self.min_p, with_logprobs=True)
        paged_decode = self._paged_decode
        counters = self._counters

        def build_paged_dispatch(K):
            def dispatch(params, token, arena, bt, pos, keys):
                """K decode steps in one program: ([B], arena, [B,MB],
                [B], [B,2]) → ([B,K] tokens, [B,K] logprobs, arena, keys,
                last, pos'). bt is LOOP-INVARIANT across the K steps —
                the loop tops up every bound stream's blocks through
                pos+K-1 first. A family that counts (``counters``) gets
                a seventh result, its counts summed over the K steps in
                one int32 vector."""

                def body(carry, _):
                    token, arena, pos, keys, counts = carry
                    logits, arena, *counted = paged_decode(
                        params, token, arena, bt, pos)
                    with jax.named_scope("sample"):
                        nxt, keys, lp = sample(logits, keys)
                    counts = {name: counts[name] + counted[0][name]
                              for name in counts}
                    return (nxt, arena, pos + 1, keys, counts), (nxt, lp)

                zeros = {name: jnp.int32(0) for name in counters}
                (token, arena, pos, keys, counts), (toks, lps) = \
                    jax.lax.scan(body, (token, arena, pos, keys, zeros),
                                 None, length=K)
                out = (jnp.transpose(toks), jnp.transpose(lps), arena,
                       keys, token, pos)
                if counters:
                    out += (jnp.stack([counts[n] for n in counters]),)
                return out

            return jax.jit(dispatch, donate_argnums=(2,))

        self._build_dispatch = build_paged_dispatch
        if self._paged_chunk_fn is not None:
            self._paged_chunk_jitted = jax.jit(self._paged_chunk_fn,
                                               donate_argnums=(2,))
        self._set_dispatch(self.K)
        self._sample_first = jax.jit(sample)

        # one jitted prefill; XLA caches one executable per bucket shape
        self._prefill_jitted = jax.jit(self._prefill_fn)
        # chunked-prefill program: ONE executable at shape [1, chunk]
        if self._chunk_fn is not None:
            self._chunk_jitted = jax.jit(self._chunk_fn,
                                         donate_argnums=(2,))
        self._jnp = jnp
        self._jax = jax

        self.speculate = 0
        self._speculate_layers: Optional[int] = None
        self._spec: Optional[dict] = None
        if int(speculate or 0) > 0:
            self.set_speculate(int(speculate), speculate_layers)

    def _set_dispatch(self, k: int) -> None:
        """Build the K-step decode program and note what it takes, so
        that its text can be had later (``decode_program_text``)."""
        import jax
        import jax.numpy as jnp

        self.K = k
        self._dispatch = self._build_dispatch(k)

        def shape(a):
            return jax.ShapeDtypeStruct(
                a.shape, a.dtype,
                sharding=a.sharding if self._mesh is not None else None)

        def host(*dims, dtype=jnp.int32):
            return jax.ShapeDtypeStruct(dims, dtype)

        _DECODE_PROGRAMS.pop(self.obs_name, None)
        _DECODE_PROGRAMS[self.obs_name] = (self._build_dispatch, k, (
            jax.tree.map(shape, self.params), host(self.B),
            jax.tree.map(shape, self._pool.arena),
            self._tables(host(self.B, self.MB), host(self.B, self.MB)),
            host(self.B), host(self.B, 2, dtype=jnp.uint32)))
        while len(_DECODE_PROGRAMS) > _DECODE_PROGRAMS_KEPT:
            del _DECODE_PROGRAMS[next(iter(_DECODE_PROGRAMS))]

    def _tables(self, bt, bt_w):
        """The block tables as the decode program takes them: the one
        table, or for a family with window layers both by arena."""
        return bt if self._window is None else {"kv": bt, "win": bt_w}

    def _calibrate_k(self) -> None:
        """steps_per_dispatch="auto": pick K from MEASURED costs.

        A decode block costs ``rtt + K·s`` wall time for ``rtt`` = the
        fixed dispatch+sync overhead (dominated by the host↔device link;
        under a millisecond on a locally attached chip) and ``s`` = one
        batched decode step. ``rtt`` is timed with a trivial synced
        device program; ``s`` falls out of one timed block at the
        initial K. K is then chosen so the fixed cost is ≤ ~20% of the
        block (K ≥ 4·rtt/s), clamped to [8, 128] and rounded down to a
        power of two (bucketed executables). Runs once, before the
        engine loop starts, on the LIVE arena (see below)."""
        import numpy as _np
        import time as _time

        jax, jnp = self._jax, self._jnp
        tiny = jax.jit(lambda x: x + 1)
        x = jnp.zeros((8,), jnp.int32)
        _np.asarray(tiny(x))  # compile off the clock
        rtt = min(
            (lambda t0: (_np.asarray(tiny(x)), _time.monotonic() - t0)[1])(
                _time.monotonic()) for _ in range(3))
        # calibrate on the LIVE arena: a throwaway one would transiently
        # double KV HBM and OOM exactly the memory-tight configs auto-K
        # serves. All-sentinel block tables: writes drop, reads hit the
        # zero block — a pure timing run that cannot corrupt the arena
        token = jnp.zeros((self.B,), jnp.int32)
        pos = jnp.zeros((self.B,), jnp.int32)
        keys = jnp.zeros((self.B, 2), jnp.uint32)
        bt = jnp.full((self.B, self.MB), self._pool.SENTINEL, jnp.int32)

        def run():
            # dispatch DONATES the arena: reassign immediately after
            # each call so a failure mid-calibration never leaves it
            # pointing at deleted buffers (start() also resets on error)
            out = self._dispatch(self.params, token, self._pool.arena, bt,
                                 pos, keys)
            self._pool.arena = out[2]
            return out

        out = run()
        _np.asarray(out[0])  # compile + warm
        t0 = _time.monotonic()
        out = run()
        _np.asarray(out[0])
        block = _time.monotonic() - t0
        step = max((block - rtt) / self.K, 1e-5)
        k = max(8, min(128, int(4 * rtt / step)))
        k = 1 << (k.bit_length() - 1)  # round down to a power of two
        log.info("serving: auto K — rtt %.2f ms, step %.3f ms → K=%d",
                 rtt * 1e3, step * 1e3, k)
        if k != self.K:
            self._set_dispatch(k)

    # -- public API -----------------------------------------------------------
    def start(self) -> "ContinuousBatchingEngine":
        if self._thread is not None and not self._thread.is_alive():
            # leftover from a timed-out stop() whose loop has since
            # exited: reap it so restart works instead of silently no-op
            self._thread.join(timeout=0)
            self._thread = None
        if self._thread is not None:
            if self._stop_evt.is_set():
                raise RuntimeError(
                    "serving: previous engine loop is still shutting "
                    "down; retry start() after it exits")
            return self  # already running
        if self._auto_k:
            self._auto_k = False  # calibrate once, not per restart
            try:
                self._calibrate_k()
            except Exception as e:  # noqa: BLE001 — auto-tune is an
                # optimization; the initial K always works
                log.warning("serving: K auto-calibration failed (%s); "
                            "keeping K=%d", e, self.K)
                # the failed dispatch may have donated (deleted) the
                # live arena's buffers or left error arrays in it
                self._pool.reset()
        self._stop_evt.clear()
        self._thread = threading.Thread(target=self._loop,
                                        name="cb-engine", daemon=True)
        self._thread.start()
        return self

    def stop(self):
        self._stop_evt.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=30)
            if self._thread.is_alive():
                # stuck in a long compile/dispatch: keep the thread ref so
                # a later start() can't spawn a concurrent second loop,
                # and leave stream state to the still-running loop
                log.warning("serving: engine loop still busy at stop(); "
                            "call stop() again after it settles")
                return
            self._thread = None
        # fail any stream still in flight so iterators don't hang; the
        # lock serializes with submit()'s running-check + enqueue, so a
        # request can't slip into _pending after this drain
        with self._lock:
            if self._partial is not None:
                self._finish_stream(self._partial[0].stream, "engine-stopped")
                self._partial = None
            for state in list(self._sstate.values()):
                self._finish_paged(state, "engine-stopped")
            if self._held is not None:
                self._finish_stream(self._held.stream, "engine-stopped")
                self._held = None
            while True:
                try:
                    req = self._pending.get_nowait()
                except _queue.Empty:
                    break
                self._finish_stream(req.stream, "engine-stopped")

    def submit(self, prompt, max_new_tokens: int = 64) -> GenerationStream:
        """Queue a prompt (sequence of int token ids); returns a
        :class:`GenerationStream` yielding generated ids."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("serving: empty prompt")
        if max_new_tokens < 1:
            raise ValueError(
                f"serving: max_new_tokens must be >= 1, got {max_new_tokens}"
                " (the prefill always yields the first token)")
        # chunked mode: the last chunk's writes (ceil(n/C)*C slots) must
        # fit the cache — equal to the plain n < S bound when C divides S
        limit = self.S - 1 if self.prefill_chunk is None else min(
            self.S - 1, (self.S // self.prefill_chunk) * self.prefill_chunk)
        if self.speculate:
            # a verify chunk writes kv at positions [pos, pos+K]; the
            # per-stream budget keeps pos <= S-1-K only if admission does
            limit = min(limit, self.S - 1 - self.speculate)
        if prompt.size > limit:
            raise ValueError(
                f"serving: prompt length {prompt.size} must be <= {limit} "
                f"(cache length {self.S}"
                + (f", prefill chunk {self.prefill_chunk})"
                   if self.prefill_chunk is not None else ")"))
        with self._lock:
            # running-check + enqueue under the same lock stop() drains
            # under, so a request can't land after the drain (it would
            # never be admitted or finished)
            if self._thread is None or self._stop_evt.is_set():
                raise RuntimeError(
                    "serving: engine is not running — call start() first "
                    "(a submit with no loop thread would never complete)")
            if self._slo is not None:
                # backlog ahead of this request: queued + admitted
                # streams (raises SloRejected before any block/queue
                # capacity is consumed — overload is turned away at the
                # door, not discovered as a latency outlier)
                backlog = self._pending.qsize() + len(self._sstate) + (
                    1 if self._held is not None else 0)
                self._slo.admit_request(_time.monotonic(), backlog)
            sid = self._next_id
            self._next_id += 1
            stream = GenerationStream(sid, prompt.size)
            stream.submit_t = _time.monotonic()
            self._pending.put(_PendingRequest(prompt, int(max_new_tokens),
                                              stream))
        self._wake.set()
        return stream

    def generate(self, prompt, max_new_tokens: int = 64,
                 timeout: Optional[float] = None) -> List[int]:
        """Synchronous helper: submit + wait (engine must be started)."""
        return self.submit(prompt, max_new_tokens).result(timeout=timeout)

    @property
    def active_streams(self) -> int:
        return len(self._sstate)

    # -- the serving path measures itself ---------------------------------------
    def _ledger(self) -> "_timeline.Timeline":
        """Where spans go: the installed timeline (an engine inside a
        traced pipeline shows on the pipeline's ledger), else its own."""
        return _timeline.ACTIVE or self.ledger

    def _enqueue(self, program, *args, **kwargs):
        """Call a device program from the loop thread and return what it
        returns: every program the loop enqueues goes through here, so
        that the loop knows when the device has nothing queued.

        The loop is synchronous and this thread alone enqueues, and the
        device runs programs in order. So the queue is empty exactly when
        the newest result a blocking fetch has returned (``_fetched``) is
        that of the newest program enqueued here. It stops being empty
        the instant this call RETURNS: the uploads (``jnp.asarray``) are
        evaluated before it as its arguments, host time in which the chip
        does next to nothing. One clock read, and only for the first
        enqueue into an empty queue; ``_phase`` does the sums."""
        out = program(*args, **kwargs)
        self._dev_enq += 1
        if self._dev_busy_t is None:
            self._dev_busy_t = _time.monotonic()
        return out

    def _fetched(self, ticket: int) -> None:
        """A blocking fetch has returned the result of the ``ticket``-th
        enqueued program (``_dev_enq`` when its call returned): it and
        every program before it have run. Takes effect when the open
        phase closes, so the bookkeeping after a fetch stays with the
        wait."""
        if ticket > self._dev_seen:
            self._dev_seen = ticket

    def _phase(self, name: str, **args) -> float:
        """Close the loop's open phase as ``name`` and open the next, with
        one clock read: whatever the loop thread did since the last call
        is ``name``. A counter and a span; returns the instant. The part
        of the phase in which nothing was queued on the device, from its
        start to its first enqueue or its end (none of it, if something
        was queued at its start), is ``starved_<name>_us`` and the span's
        ``starved_us``."""
        now = _time.monotonic()
        t0, self._phase_t = self._phase_t, now
        us = int(now * 1e6) - int(t0 * 1e6)  # whole numbers tile exactly
        self.stats[f"phase_{name}_us"] += us
        self.stats["loop_us"] += us
        self._iter_us[name] = self._iter_us.get(name, 0) + us
        if name in STARVED_PHASES:
            until = now if self._dev_busy_t is None else self._dev_busy_t
            starved = int(until * 1e6) - int(t0 * 1e6)
            if starved:
                self.stats[f"starved_{name}_us"] += starved
                self.stats["starved_us"] += starved
                self._iter_starved[name] = \
                    self._iter_starved.get(name, 0) + starved
                args["starved_us"] = starved
        # a fetch takes effect here: what is still unread is queued from
        # the next phase's first instant
        self._dev_busy_t = None if self._dev_seen == self._dev_enq else now
        led = self._ledger()
        # consecutive waits are one record: an idle loop keeps its history
        if not (name == "idle" and led.extend_last("lm_idle", now)):
            led.span("lm_" + name, None, t0, now, track=self.obs_name,
                     dispatch=self.stats["dispatches"], **args)
        return now

    def _end_iteration(self) -> None:
        """Top of the loop. The iteration that just ended is held against
        the running median of those before it: the stall note."""
        phases, self._iter_us = self._iter_us, {}
        starved, self._iter_starved = self._iter_starved, {}
        total = sum(phases.values())
        if not total:
            return
        median = self._iter_median.quantile()
        self._iter_median.observe(total)
        if median is not None and total > STALL_MIN_S * 1e6 \
                and total > STALL_FACTOR * median:
            self.stats["stalls"] += 1
            log.warning(
                "serving: %s iteration %d ms: %s", self.obs_name,
                total // 1000, ", ".join(
                    f"{k} {v // 1000}" + (
                        f" (starved {starved[k] // 1000})"
                        if starved.get(k, 0) >= 1000 else "")
                    for k, v in sorted(
                        phases.items(), key=lambda kv: -kv[1])))

    def _begin_admission(self, req: _PendingRequest) -> None:
        """The loop has taken ``req`` to admit it: the second stamp."""
        st = req.stream
        st.admit_t = _time.monotonic()
        self._m_queue_wait.observe(st.admit_t - st.submit_t)

    def _read_first_stamps(self, st: GenerationStream) -> None:
        """The first token has reached its stream (the third stamp): what
        reads the first three."""
        self._lm_stats.observe_ttft(st.first_t - st.submit_t)
        self.stats["admissions"] += 1
        self.stats["admit_wait_us"] += int((st.admit_t - st.submit_t) * 1e6)
        self.stats["first_token_us"] += int((st.first_t - st.admit_t) * 1e6)

    def _finish_stream(self, st: GenerationStream, reason: str) -> None:
        """Every finish the engine decides: the fourth stamp, the time
        per output token this client saw, and the request as one async
        span in the ledger with marks at its admission and first token
        (recorded whole, after the fact) — all before the client wakes."""
        if st.finished:
            return
        st.finish_t = _time.monotonic()
        if st.first_t is not None and len(st.tokens) > 1:
            self._lm_stats.observe_token(
                (st.finish_t - st.first_t) / (len(st.tokens) - 1))
        led, track, sid = self._ledger(), self.obs_name, st.stream_id
        led.async_begin("lm_request", sid, st.submit_t, track)
        if st.admit_t is not None:
            led.mark("lm_admitted", None, st.admit_t, track, stream=sid)
        if st.first_t is not None:
            led.mark("lm_first", None, st.first_t, track, stream=sid)
        led.async_end("lm_request", sid, st.finish_t, track)
        st._finish(reason)

    # -- engine internals ------------------------------------------------------
    def _bucket(self, n: int) -> int:
        b = self.min_bucket
        while b < n:
            b *= 2
        return min(b, self.S)

    def _prefill_rows(self, bucket: int) -> int:
        """The prompts the ONE prefill program of ``bucket`` takes
        (``models/family.py prefill_rows``); 1 under speculation, whose
        admission binds a draft slot a prompt."""
        from nnstreamer_tpu.models.family import prefill_rows

        if self._spec is not None:
            return 1
        return prefill_rows(self._balance, bucket)

    #: process-wide sequence behind ``obs_name`` (engine0, engine1, ...)
    _OBS_SEQ = itertools.count()

    #: minimum common-prefix length worth a warm (remainder-only)
    #: admission; exact whole-prompt hits are never thresholded
    PREFIX_MIN_REUSE = 4

    def _advance_partial(self):
        """Run ONE prefill chunk; the last one scatters the finished
        batch-1 cache into fresh blocks and activates the stream."""
        jnp = self._jnp
        req, cache1, k = self._partial
        C = self.prefill_chunk
        prompt, n = req.prompt, req.prompt.size
        start = k * C
        end = min(start + C, n)
        chunk = np.zeros((1, C), np.int32)
        chunk[0, :end - start] = prompt[start:end]
        who = req.who()
        try:
            logits, cache1 = self._enqueue(
                self._chunk_jitted, self.params, jnp.asarray(chunk), cache1,
                jnp.asarray(start, jnp.int32))
            self.stats["prefill_chunks"] += 1
            self.stats["prefill_tokens"] += end - start
            self.stats["prefill_bucket_tokens"] += C
            if end < n:
                self._partial = (req, cache1, k + 1)
                self._phase("admit", **who)
                return
            # final chunk: logits at the prompt's true last position
            self._partial = None
            logits_last = logits[:, (n - 1) - start]
            rec = self._activate_paged_from_cache1(req, logits_last, cache1)
            if rec is None:  # pool exhausted: re-ingest when it isn't
                self.stats["kv_defers"] += 1
                self._held = req
            else:
                self._phase("admit", **who)
                self._activate_commit_paged(rec)
                self._phase("first_token", **who)
        except Exception as e:  # noqa: BLE001 — a failed chunk must fail
            # only this request
            log.warning("serving: chunked prefill failed: %s", e)
            self._partial = None
            self._finish_stream(req.stream, f"error: {e}")

    def _recover(self, e) -> None:
        """Device failure: fail every admitted stream, the held request
        and any half-ingested prompt, rebuild the (possibly donated-away)
        arena, and keep serving."""
        log.error("serving: dispatch failed: %s", e)
        if self._partial is not None:
            self._finish_stream(self._partial[0].stream, f"error: {e}")
            self._partial = None
        for state in list(self._sstate.values()):
            self._finish_stream(state["stream"], f"error: {e}")
        self._sstate.clear()
        if self._held is not None:
            self._finish_stream(self._held.stream, f"error: {e}")
            self._held = None
        self._lane = [None] * self.B
        self._dev_seen = self._dev_enq  # nothing of the old queue is awaited
        # the arena may hold donated-away/error buffers; a fresh one is
        # the same bytes, so accounting is unchanged. Prefix entries hold
        # block ids into the dead allocation map — drop them with it.
        self._pool.reset()
        self._bt[:] = self._pool.SENTINEL
        if self._bt_w is not None:
            self._bt_w[:] = self._pool.win.SENTINEL
        self._prefix.clear()
        self._prefix_trie = _PrefixTrie()
        if self._spec is not None:
            self._spec["dcache"] = None
            self._spec["dcache"] = self._spec["init_dcache"]()

    # -- speculative decoding (speculate=K) -----------------------------------
    def set_speculate(self, k: int,
                      draft_layers: Optional[int] = None) -> None:
        """Reconfigure speculative decoding (the ``speculate=K`` knob on
        tensor_lm_serve). No-op when unchanged; requires a stopped
        engine loop — the draft cache and jitted round program are
        rebuilt. ``k=0`` disables."""
        k = int(k or 0)
        if k == self.speculate and (
                k == 0 or draft_layers == self._speculate_layers):
            return
        if self._thread is not None and self._thread.is_alive():
            raise RuntimeError(
                "serving: set_speculate requires a stopped engine loop")
        if k < 0:
            raise ValueError(f"serving: speculate must be >= 0, got {k}")
        if k >= self.S:
            raise ValueError(
                f"serving: speculate ({k}) must be < max_seq ({self.S})")
        self.speculate = k
        self._speculate_layers = draft_layers
        self._spec = None
        if k:
            self._build_speculative()

    def _build_speculative(self) -> None:
        """One jitted program per round: γ greedy draft steps (a
        ``draft_layers``-deep prefix slice of the target,
        models/speculative.py), then the target VERIFIES all γ+1
        positions in a single chunk pass — per-row argmax match gives
        n_emit ∈ [1, γ+1] tokens whose values are exactly what
        non-speculative greedy decoding would emit (the target argmax
        is ground truth; drafts only decide how many positions one
        round advances). A rejected draft costs nothing to undo: the
        host simply advances pos by n_emit, and the stale kv above it
        is overwritten before it is ever attended (the next round's
        chunk covers it) — the roll-back is the block-table tail
        pointer, no block copies. The draft keeps a contiguous cache of
        its own, one slot per decode lane."""
        if self.temperature > 0:
            raise ValueError(
                "serving: speculate requires greedy decoding "
                "(temperature=0) — draft/verify parity is exact only "
                "for argmax")
        if self._mesh is not None:
            raise ValueError(
                "serving: speculate does not compose with mesh= (the "
                "draft cache is slot-structured, not sharded)")
        jax, jnp = self._jax, self._jnp
        from nnstreamer_tpu.models.speculative import draft_from_target
        from nnstreamer_tpu.models.transformer import (
            build_decode_step,
            build_prefill,
            init_cache,
        )

        cfg = self.cfg
        nl = self._speculate_layers or max(1, cfg.n_layers // 2)
        dcfg, dparams = draft_from_target(cfg, self.params, nl)
        draft_decode = build_decode_step(dcfg, self.S)
        g = self.speculate

        def init_dcache():
            return init_cache(dcfg, self.B, self.S)

        def draft_and_verify(params, dparams, token, dcache, pos,
                             verify):
            """Shared skeleton; ``verify(chunk_toks)`` runs the target
            chunk and returns [b, γ+1, V] logits."""

            def dbody(carry, _):
                tok, dc, p = carry
                lg, dc = draft_decode(dparams, tok, dc, p)
                nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                return (nxt, dc, p + 1), nxt

            (_tok, dcache, _p), drafts = jax.lax.scan(
                dbody, (token, dcache, pos), None, length=g)
            drafts = jnp.transpose(drafts)                 # [b, γ]
            chunk_toks = jnp.concatenate([token[:, None], drafts],
                                         axis=1)           # [b, γ+1]
            logits = verify(chunk_toks)
            tgt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            lps = jnp.take_along_axis(
                jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1),
                tgt[..., None], axis=-1)[..., 0]
            match = (tgt[:, :g] == drafts).astype(jnp.int32)
            n_emit = jnp.sum(jnp.cumprod(match, axis=1), axis=1) + 1
            # draft-cache catch-up: (re)write the kv of the LAST emitted
            # token at its position. For m <= γ it is an idempotent
            # rewrite; for a full accept (m = γ+1) it fills the one
            # position the draft scan never wrote, keeping the draft
            # cache canonical (this affects acceptance rate only —
            # correctness is the target's verify either way)
            fix = jnp.where(
                n_emit == 1, token,
                jnp.take_along_axis(
                    tgt, jnp.maximum(n_emit - 2, 0)[:, None], 1)[:, 0])
            _lg, dcache = draft_decode(dparams, fix, dcache,
                                       pos + n_emit - 1)
            return tgt, lps, n_emit, dcache

        pchunk = self._paged_chunk_fn

        def spec_round(params, dparams, token, arena, bt, dcache, pos):
            out_box = []  # closure cell for the updated arena tree

            def verify(chunk_toks):
                b = chunk_toks.shape[0]
                logits, new_arena = pchunk(
                    params, chunk_toks, arena, bt, pos,
                    jnp.full((b,), g + 1, jnp.int32))
                out_box.append(new_arena)
                return logits

            tgt, lps, n_emit, dcache = draft_and_verify(
                params, dparams, token, dcache, pos, verify)
            return tgt, lps, n_emit, out_box[0], dcache

        def insert(dcache, dcache1, slot):
            # a prompt's batch-1 draft cache over lane ``slot``'s (batch
            # axis 2) of the draft's [L,2,B,S,h,dh] cache
            return jax.tree.map(
                lambda c, u: jax.lax.dynamic_update_slice(
                    c, u.astype(c.dtype),
                    (0, 0, slot) + (0,) * (c.ndim - 3)), dcache, dcache1)

        self._spec = {
            "dparams": dparams, "dcfg": dcfg,
            "dcache": init_dcache(), "init_dcache": init_dcache,
            "prefill": jax.jit(build_prefill(dcfg, self.S)),
            "insert": jax.jit(insert, donate_argnums=(0,)),
            "dispatch": jax.jit(spec_round, donate_argnums=(3, 5)),
        }

    def _draft_prefill(self, req: _PendingRequest, slot: int) -> None:
        jnp = self._jnp
        sp = self._spec
        n = req.prompt.size
        bucket = self._bucket(n)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = req.prompt
        _lg, dcache1 = self._enqueue(
            sp["prefill"], sp["dparams"], jnp.asarray(padded),
            lengths=jnp.asarray(np.asarray([n], np.int32)))
        sp["dcache"] = self._enqueue(sp["insert"], sp["dcache"], dcache1,
                                     slot)

    def _spec_step_paged(self) -> None:
        jnp = self._jnp
        sp = self._spec
        g = self.speculate
        run = []
        for st in list(self._sstate.values()):
            if self._sstate.get(st["sid"]) is not st:
                continue
            if not self._topup(st):
                continue
            slot = st["slot"]
            self._bt[slot, :] = self._pool.SENTINEL
            self._bt[slot, :len(st["blocks"])] = st["blocks"]
            run.append(st)
        if not run:
            self._wake_streams()
            return
        last = np.zeros(self.B, np.int32)
        pos = np.zeros(self.B, np.int32)
        for st in run:
            last[st["slot"]] = st["last"]
            pos[st["slot"]] = st["pos"]
        t0 = self._phase("select")
        tgt, lps, n_emit, arena, dcache = self._enqueue(
            sp["dispatch"], self.params, sp["dparams"], jnp.asarray(last),
            self._pool.arena, jnp.asarray(self._bt), sp["dcache"],
            jnp.asarray(pos))
        self._pool.arena = arena
        sp["dcache"] = dcache
        _start_host_copies(tgt, lps, n_emit)
        self._wake_streams()
        tgt, lps, n_emit = np.asarray(tgt), np.asarray(lps), np.asarray(n_emit)
        self._fetched(self._dev_enq)
        self.invoke_stats.record(self._phase("dispatch") - t0)
        self.stats["slot_steps"] += self.B * (g + 1)
        run = [st for st in run if self._sstate.get(st["sid"]) is st]
        rows = [st["slot"] for st in run]
        m = n_emit[rows]
        self.stats["spec_drafted"] += g * len(run)
        self.stats["spec_accepted"] += int(m.sum()) - len(run)
        # rejected drafts roll the block-table tail pointer back by
        # construction: pos advances only m, and the stale kv above it is
        # overwritten before it is ever attended
        self._hand_over(run, rows, tgt, lps, m)
        self._phase("emit")
        self.stats["dispatches"] += 1

    # -- blocks, prefix cache, admission, decode --------------------------------
    def _blocks_for(self, n: int) -> int:
        """Blocks a fresh n-token-prompt stream needs up front: the
        prompt's positions plus the first decode write (always
        n//T + 1 — the tail block doubles as the decode block unless
        the prompt ends exactly on a boundary)."""
        return n // self.block_tokens + 1

    def _window_first(self, pos: int) -> int:
        """The first block of the window table that a step at position
        ``pos`` reads: the one that holds ``pos - window + 1``."""
        if self._window is None:
            return 0
        return max(0, pos - self._window[1] + 1) // self.block_tokens

    def _release_behind(self, state) -> None:
        """Give back the blocks of ``state``'s window table that lie
        wholly before ``pos - window + 1``: no step reads them again."""
        first = self._window_first(state["pos"])
        gone = min(first - state["wfirst"], len(state["wblocks"]))
        if gone > 0:
            self._pool.win.release(state["wblocks"][:gone])
            del state["wblocks"][:gone]
            state["wfirst"] += gone
            self.stats["kv_window_blocks_released"] += gone

    def _alloc_blocks(self, k: int):
        """Pool alloc with the evict rung of the pressure ladder: LRU
        paged prefix entries are dropped until the allocation fits (or
        nothing is left to drop — the caller then defers or sheds)."""
        ids = self._pool.alloc(k)
        while ids is None and self._evict_prefix_paged():
            ids = self._pool.alloc(k)
        return ids

    def _evict_prefix_paged(self) -> bool:
        if not self._prefix:
            return False
        from nnstreamer_tpu.tensors import memory as _memory

        evicted, (ids, _logits) = self._prefix.popitem(last=False)
        self._prefix_trie.remove(evicted)
        self._pool.release(list(ids))
        acct = _memory.ACTIVE
        if acct is not None:
            acct.count_pressure("evict")
        return True

    def _prefix_lookup_paged(self, prompt: np.ndarray):
        """→ (lcp, entry key, logits). Longest common prefix between
        ``prompt`` and a cached entry; logits only on an exact
        whole-prompt == whole-key hit. Reuse happens at BLOCK
        granularity (the caller rounds down)."""
        if not self.prefix_cache:
            return 0, None, None
        best_key, lcp = self._prefix_trie.lookup(prompt)
        if best_key is None or lcp <= 0:
            return 0, None, None
        self._prefix.move_to_end(best_key)
        _ids, logits = self._prefix[best_key]
        if not (lcp == prompt.size == len(best_key)):
            logits = None
        return lcp, best_key, logits

    def _prefix_store_paged(self, prompt: np.ndarray, blocks,
                            logits) -> None:
        """Retain the stream's prompt-covering blocks as a cache entry:
        sharing is a refcount bump, so a prefix costs its blocks ONCE
        and reuse is exact by construction (same physical kv). The tail
        block may be partial; every reader takes a COW copy of it, and
        the donor stream's later appends land at offsets >= n % T —
        outside the entry's [0, n) range."""
        if not self.prefix_cache:
            return
        key = tuple(int(t) for t in prompt)
        if key in self._prefix:
            return
        n = prompt.size
        T = self.block_tokens
        ids = tuple(blocks[:(n + T - 1) // T])
        self._pool.retain(ids)
        self._prefix_trie.insert(key)
        self._prefix[key] = (ids, logits)
        self._prefix.move_to_end(key)
        while len(self._prefix) > self.prefix_cache:
            evicted, (eids, _lg) = self._prefix.popitem(last=False)
            self._prefix_trie.remove(evicted)
            self._pool.release(list(eids))

    def _admit_paged(self, req: _PendingRequest):
        """Admission: allocate the stream's block table, prefill
        cold / block-aligned warm / exact-hit, and return the
        activation record — or None to DEFER when the pool cannot
        cover the prompt (admission is bounded by FREE BLOCKS, not
        batch slots; the caller holds the request so FIFO order keeps).
        Deferral is cheap: every path allocates before device work."""
        self._begin_admission(req)
        jnp = self._jnp
        prompt = req.prompt
        n = prompt.size
        T = self.block_tokens
        p, key_hit, cached_logits = self._prefix_lookup_paged(prompt)
        if cached_logits is not None:  # exact whole-prompt hit
            eids, _lg = self._prefix[key_hit]
            fresh = self._alloc_blocks(1)
            if fresh is None:
                return None
            full = n // T
            shared = list(eids[:full])
            self._pool.retain(shared)
            blocks = shared + fresh
            try:
                if n % T:
                    # COW fault: private copy of the entry's partial
                    # tail — the stream appends there from offset n % T
                    self._enqueue(self._pool.copy_block, eids[full],
                                  fresh[0])
                self.stats["prefix_hits"] += 1
                self.stats["prefix_tokens_reused"] += n
                return self._activate_begin_paged([req], cached_logits,
                                                  [blocks])
            except Exception:
                self._pool.release(blocks)
                raise
        q = min((p // T) * T, ((n - 1) // T) * T)  # block-aligned reuse
        if (key_hit is not None
                and q >= max(T, self.PREFIX_MIN_REUSE)
                and q + self._bucket(n - q) <= self.S):
            eids, _lg = self._prefix[key_hit]
            shared = list(eids[:q // T])
            fresh = self._alloc_blocks(self._blocks_for(n) - len(shared))
            if fresh is None:
                return None
            self._pool.retain(shared)
            blocks = shared + fresh
            try:
                self.stats["prefix_hits"] += 1
                self.stats["prefix_tokens_reused"] += q
                rem = n - q
                c = self._bucket(rem)
                toks = np.zeros((1, c), np.int32)
                toks[0, :rem] = prompt[q:]
                bt = np.full((1, self.MB), self._pool.SENTINEL, np.int32)
                bt[0, :len(blocks)] = blocks
                logits, arena = self._enqueue(
                    self._paged_chunk_jitted,
                    self.params, jnp.asarray(toks), self._pool.arena,
                    jnp.asarray(bt), jnp.asarray(np.asarray([q], np.int32)),
                    jnp.asarray(np.asarray([rem], np.int32)))
                self._pool.arena = arena
                self.stats["prefill_tokens"] += rem
                self.stats["prefill_bucket_tokens"] += c
                logits = logits[:, rem - 1]
                self._prefix_store_paged(prompt, blocks, logits)
                return self._activate_begin_paged([req], logits, [blocks])
            except Exception:
                self._pool.release(blocks)
                raise
        blocks = self._alloc_blocks(self._blocks_for(n))
        if blocks is None:
            return None
        # a family with window layers: the blocks of the window arena that
        # the first decode step reads and writes, from the block that
        # holds position n - window + 1 on; either arena defers
        wfirst, wblocks = self._window_first(n), []
        if self._window is not None:
            wblocks = self._pool.win.alloc(self._blocks_for(n) - wfirst)
            if wblocks is None:
                self._pool.release(blocks)
                return None
        # a family with lane state: the stream's lane is claimed here, and
        # the prefill's final state goes over that lane's slot whole
        lane = self._pool.alloc_lane() if self._lane_state else None
        if self._lane_state and lane is None:  # every lane taken: defer
            self._pool.release(blocks)
            if wblocks:
                self._pool.win.release(wblocks)
            return None
        cold = _ColdAdmission(req, blocks, wblocks, wfirst, lane,
                              self._bucket(n))
        if self._prefill_rows(cold.bucket) > 1:
            return cold  # prefilled with the boundary's others
        return self._prefill_group([cold], 1)

    def _prefill_groups(self, cold: List[_ColdAdmission]) -> List[tuple]:
        """A loop boundary's cold admissions as prefill programs:
        ``[(admissions, rows)]``. A program's bucket is its longest
        prompt's and it takes up to that bucket's rows; the admissions are
        taken greedily, shortest bucket first (in admission order within a
        bucket), so that a short prompt rides with prompts of its own
        bucket: a row costs its program's bucket whether or not it holds a
        prompt that long."""
        groups: List[tuple] = []
        for c in sorted(cold, key=lambda c: c.bucket):
            if groups:
                group, _ = groups[-1]
                rows = self._prefill_rows(
                    max(c.bucket, max(g.bucket for g in group)))
                if len(group) < rows:
                    group.append(c)
                    groups[-1] = (group, rows)
                    continue
            groups.append(([c], self._prefill_rows(c.bucket)))
        return groups

    def _prefill_group(self, group: List[_ColdAdmission], rows: int):
        """ONE prefill program over ``rows`` rows, of which the first hold
        ``group``'s prompts right-padded to the bucket of the longest (a
        row past them holds no prompt: length 0, no block, no lane); ONE
        scatter of every row's cache and state; ONE first-token sample over
        the rows. The activation record of the group's streams."""
        jnp = self._jnp
        T = self.block_tokens
        bucket = max(c.bucket for c in group)
        tokens = np.zeros((rows, bucket), np.int32)
        lengths = np.zeros(rows, np.int32)
        for i, c in enumerate(group):
            lengths[i] = c.req.prompt.size
            tokens[i, :lengths[i]] = c.req.prompt
        try:
            logits, cache = self._enqueue(
                self._prefill_jitted, self.params, jnp.asarray(tokens),
                lengths=jnp.asarray(lengths))
            self.stats["prefill_programs"] += 1
            self.stats["prefill_tokens"] += int(lengths.sum())
            self.stats["prefill_bucket_tokens"] += rows * bucket
            if self._prefill_counters:
                for name, n in self._prefill_counters(self.cfg,
                                                      bucket).items():
                    self.stats[name] += rows * n
            if rows == 1:
                # the pool's one-prompt hand-over, as before rows (the
                # benchmark's window control, ``benchmark/controls_afmoe.py``,
                # wraps it); a prefix entry keeps a one-prompt program's
                # logits
                c = group[0]
                block_ids, lane, window_ids, wfirst = c.scatter_row(T)
                self._enqueue(self._pool.scatter_prefill, cache, block_ids,
                              lane=lane, window_ids=window_ids,
                              window_first=wfirst)
                self._prefix_store_paged(c.req.prompt, c.blocks, logits)
            else:
                self._enqueue(self._pool.scatter_rows, cache,
                              [c.scatter_row(T) for c in group]
                              + [None] * (rows - len(group)))
            rec = self._activate_begin_paged(
                [c.req for c in group], logits, [c.blocks for c in group],
                [c.lane for c in group])
            for state, c in zip(rec[1], group):
                state.update(wblocks=c.wblocks, wfirst=c.wfirst)
            return rec
        except Exception:
            for c in group:
                c.release(self._pool)
            raise

    def _activate_paged_from_cache1(self, req: _PendingRequest, logits,
                                    cache1):
        """Chunked-prefill commit: scatter the finished batch-1 cache
        into fresh blocks. None = pool exhausted (caller re-holds)."""
        n = req.prompt.size
        T = self.block_tokens
        blocks = self._alloc_blocks(self._blocks_for(n))
        if blocks is None:
            return None
        try:
            self._enqueue(self._pool.scatter_prefill, cache1,
                          blocks[:(n + T - 1) // T])
            self._prefix_store_paged(req.prompt, blocks, logits)
            return self._activate_begin_paged([req], logits, [blocks])
        except Exception:
            self._pool.release(blocks)
            raise

    def _begin_partial_paged(self, req: _PendingRequest) -> None:
        """Chunked prompt ingestion: chunks build a batch-1 contiguous
        cache that the FINAL chunk scatters into fresh blocks — no lane
        is reserved, blocks allocate at activation. (Prefix reuse is
        not wired on this path; chunked prompts ingest from 0.)"""
        from nnstreamer_tpu.models.transformer import init_cache

        self._begin_admission(req)
        self._partial = (req, self._enqueue(
            init_cache, self.cfg, 1, self.S, kv_codec=self.kv_quant), 0)

    def _activate_begin_paged(self, reqs: List[_PendingRequest], logits,
                              blocks: List[list], lanes=None):
        """Device half of an activation: sample the first token of each
        of ``reqs`` (row i of ``logits``; a row past them holds no prompt)
        in one program, create the streams' decode states. No lane is
        claimed (EDF binds lanes per dispatch) — except where a stream is
        pinned to a lane for life: in speculative mode (the draft cache is
        slot-structured) and for a family with lane state, whose admission
        claimed ``lanes[i]`` before its prefill was scattered. The record
        ``(reqs, states, first, keys, logprobs, ticket)``."""
        jnp = self._jnp
        keys = np.zeros((logits.shape[0], 2), np.uint32)
        keys[:len(reqs), 0] = self.seed & 0xFFFFFFFF
        keys[:len(reqs), 1] = [r.stream.stream_id & 0xFFFFFFFF
                               for r in reqs]
        first_d, key_d, lp_d = self._enqueue(self._sample_first, logits,
                                             jnp.asarray(keys))
        ticket = self._dev_enq  # what the first token's fetch will prove run
        slo_s = self._slo.budget_s if self._slo is not None else 60.0
        states = []
        for req, ids, lane in zip(reqs, blocks, lanes or [None] * len(reqs)):
            stream = req.stream
            sid = stream.stream_id
            n = req.prompt.size
            state = {
                "sid": sid, "stream": stream, "blocks": list(ids),
                "pos": n, "last": 0, "key": np.zeros(2, np.uint32),
                # cap writes inside S (a verify chunk writes through pos+K)
                "budget": min(req.max_new, self.S - n - self.speculate),
                #: absolute deadline feeding the per-token EDF key
                "deadline_t": stream.submit_t + slo_s,
                "slot": None,
            }
            self._sstate[sid] = state
            if lane is not None:
                self._lane[lane] = sid
                state["slot"] = stream.lane = lane
            elif self._spec is not None:
                slot = self._lane.index(None)
                self._lane[slot] = sid
                state["slot"] = slot
                self._draft_prefill(req, slot)
            states.append(state)
        return (reqs, states, first_d, key_d, lp_d, ticket)

    def _activate_commit_paged(self, rec) -> None:
        reqs, states, first_d, key_d, lp_d, ticket = rec
        self.stats["prefills"] += len(states)
        first, lp, key = (np.asarray(first_d), np.asarray(lp_d),
                          np.asarray(key_d))
        # the last admitted record of a boundary empties the queue; the
        # earlier ones' tickets lie behind programs still queued
        self._fetched(ticket)
        self._hand_over(states, range(len(states)), first[:, None],
                        lp[:, None], 1, keys=key, first=True)

    def _hand_over(self, run, rows, toks, lps, counts, keys=None,
                   first: bool = False) -> None:
        """Give each stream of ``run`` what one program made for it, as
        ONE block: row ``rows[i]`` of ``toks`` / ``lps`` (``[b, n]``), of
        which the first ``counts[i]`` (or ``counts`` for every row) are
        tokens. A stream keeps them up to and including the first
        ``eos_id`` and at most its budget, found with numpy over the
        block; then one put a stream, so that its client wakes once. Its
        last token, budget and position follow (a first token moves no
        position), its key is row ``rows[i]`` of ``keys`` where given. A
        stream that ends finishes ``eos`` or ``length`` with its block
        put before its end mark, its blocks and lane back in the pool
        before the end mark wakes the client; one of a family with window
        layers that goes on gives back what now lies behind its window.
        The block of a stream that goes on is put once the next dispatch
        is queued (``_wake_streams``): its client's thread then runs
        while the device works, not between two dispatches. ``first``:
        the first token of each stream, put at once, with the stamps it
        completes."""
        rows = np.asarray(rows, np.int64)
        toks, lps = toks[rows], lps[rows]
        counts = np.broadcast_to(counts, rows.shape)
        keep = np.minimum(counts, np.fromiter(
            (st["budget"] for st in run), np.int64, len(run)))
        eos = np.zeros(rows.shape, bool)
        if self.eos_id is not None:
            hit = toks == self.eos_id
            at = np.where(hit.any(axis=1), hit.argmax(axis=1), toks.shape[1])
            eos = at < keep
            keep = np.where(eos, at + 1, keep)
        total = int(keep.sum())
        self.stats["tokens_generated"] += total
        if not first:
            self.stats["active_slot_steps"] += total
        self.stats["emit_blocks"] += len(run)
        last = toks[np.arange(len(run)), counts - 1].tolist()
        toks, lps = toks.tolist(), lps.tolist()
        for i, st in enumerate(run):
            k = int(keep[i])
            st["stream"]._emit_block(toks[i][:k], lps[i][:k], wake=first)
            st["budget"] -= k
            st["last"] = last[i]
            if keys is not None:
                st["key"] = keys[rows[i]].copy()
            if first:
                self._read_first_stamps(st["stream"])
            else:
                st["pos"] += int(counts[i])
            if eos[i] or st["budget"] <= 0:
                if self._slo is not None:
                    now = _time.monotonic()
                    t0 = st["stream"].submit_t
                    self._slo.observe_completion(now - t0, now, frames=1)
                    self._slo.observe_service(now - t0, frames=1)
                self._finish_paged(st, "eos" if eos[i] else "length")
            elif not first:
                self._unwoken.append(st["stream"])
                if self._window is not None:
                    self._release_behind(st)

    def _wake_streams(self) -> None:
        """Put the blocks the last hand-over held back."""
        streams, self._unwoken = self._unwoken, []
        for stream in streams:
            stream._wake()

    def _finish_paged(self, state, reason: str) -> None:
        """Stream teardown: blocks return to the pool BEFORE the client
        wakes, so a caller that observes its stream done also observes
        the capacity released."""
        self._sstate.pop(state["sid"], None)
        slot = state["slot"]
        if slot is not None:
            self._lane[slot] = None
            self._bt[slot, :] = self._pool.SENTINEL
            if self._bt_w is not None:
                self._bt_w[slot, :] = self._pool.win.SENTINEL
            state["slot"] = None
            if self._lane_state:
                self._pool.release_lane(slot)
        if state["blocks"]:
            state["stream"].blocks = tuple(state["blocks"])
            self._pool.release(state["blocks"])
            state["blocks"] = []
        if state.get("wblocks"):
            state["stream"].window_blocks = (state["wfirst"],
                                             tuple(state["wblocks"]))
            self._pool.win.release(state["wblocks"])
            state["wblocks"] = []
        self._finish_stream(state["stream"], reason)

    def _shed_one(self, keep_sid: int) -> bool:
        """Decode-time block exhaustion: revoke the MOST-LATE admitted
        stream's blocks (deepest past deadline), replaying the
        admission-revocation accounting — pressure rung "shed", the
        SLO scheduler's shed counters, finish reason "shed". False =
        the only candidate was ``keep_sid`` itself (the caller gives
        that stream up — self-shed)."""
        from nnstreamer_tpu.tensors import memory as _memory

        cands = [st for st in self._sstate.values()
                 if st["sid"] != keep_sid]
        self_shed = not cands
        if self_shed:
            victim = self._sstate.get(keep_sid)
            if victim is None:
                return False
        else:
            victim = min(cands, key=lambda st: st["deadline_t"])
        now = _time.monotonic()
        late = victim["deadline_t"] <= now
        acct = _memory.ACTIVE
        if acct is not None:
            acct.count_pressure("shed")
        if self._slo is not None:
            self._slo.note_shed_request(now, late)
        self.stats["kv_sheds"] += 1
        log.warning("serving: paged KV exhausted — shedding stream %d "
                    "(%s)", victim["sid"], "late" if late else "capacity")
        self._finish_paged(victim, "shed")
        return not self_shed

    def _topup(self, state) -> bool:
        """Grow ``state``'s block table to cover the whole next
        dispatch block (pos+K-1; pos+K for a speculative verify),
        walking the evict → shed ladder on exhaustion. False = the
        stream itself was shed."""
        steps = (self.speculate + 1) if self._spec is not None else self.K
        # a dispatch whose last steps pass max_seq writes them at S-1 (the
        # programs clamp; the budget ends the stream before they are read)
        hi = min((state["pos"] + steps - 1) // self.block_tokens,
                 self.MB - 1)
        while len(state["blocks"]) <= hi:
            ids = self._alloc_blocks(hi + 1 - len(state["blocks"]))
            if ids is None:
                if not self._shed_one(state["sid"]):
                    return False
                continue
            state["blocks"].extend(ids)
        # the window table through the same block; what lies behind the
        # window went back after the last dispatch (``_release_behind``)
        while self._window is not None and \
                state["wfirst"] + len(state["wblocks"]) <= hi:
            ids = self._pool.win.alloc(
                hi + 1 - state["wfirst"] - len(state["wblocks"]))
            if ids is None:
                if not self._shed_one(state["sid"]):
                    return False
                continue
            state["wblocks"].extend(ids)
        return True

    def _decode_step_paged(self) -> None:
        """One EDF-scheduled K-step decode block: bind the B most
        urgent streams (per-TOKEN deadline — a nearly-late short
        stream preempts a long one at block granularity), top up their
        block tables, run the ONE jitted paged program, emit."""
        jnp = self._jnp
        from nnstreamer_tpu.serving.scheduler import token_deadline

        now = _time.monotonic()
        states = list(self._sstate.values())
        if len(states) > self.B:
            states.sort(key=lambda st: token_deadline(
                now, st["deadline_t"], st["budget"]))
            selected = states[:self.B]
            keep = {st["sid"] for st in selected}
            # park preempted streams' lanes (their kv lives in the
            # arena; state re-binds whenever EDF selects them again)
            for slot, sid in enumerate(self._lane):
                if sid is not None and sid not in keep:
                    parked = self._sstate.get(sid)
                    if parked is not None:
                        parked["slot"] = None
                    self._lane[slot] = None
                    self._bt[slot, :] = self._pool.SENTINEL
                    if self._bt_w is not None:
                        self._bt_w[slot, :] = self._pool.win.SENTINEL
        else:
            selected = states
        run = []
        for st in selected:
            if self._sstate.get(st["sid"]) is not st:
                continue  # shed while topping up an earlier stream
            if not self._topup(st):
                continue  # self-shed
            if st["slot"] is None:
                slot = self._lane.index(None)
                self._lane[slot] = st["sid"]
                st["slot"] = slot
            slot = st["slot"]
            self._bt[slot, :] = self._pool.SENTINEL
            self._bt[slot, :len(st["blocks"])] = st["blocks"]
            if self._bt_w is not None:
                lo = st["wfirst"]
                self._bt_w[slot, :] = self._pool.win.SENTINEL
                self._bt_w[slot, lo:lo + len(st["wblocks"])] = st["wblocks"]
            run.append(st)
        if not run:
            self._wake_streams()
            return
        last = np.zeros(self.B, np.int32)
        pos = np.zeros(self.B, np.int32)
        keys = np.zeros((self.B, 2), np.uint32)
        for st in run:
            last[st["slot"]] = st["last"]
            pos[st["slot"]] = st["pos"]
            keys[st["slot"]] = st["key"]
        steps = np.minimum(pos[:, None] + np.arange(self.K), self.S - 1)
        self.stats["kv_blocks_live"] += int(
            (steps // self.block_tokens + 1).sum())
        self.stats["kv_blocks_table"] += self.B * self.MB * self.K
        if self._window is not None:
            oldest = np.maximum(steps - self._window[1] + 1, 0)
            self.stats["kv_window_blocks_live"] += int(
                (steps // self.block_tokens
                 - oldest // self.block_tokens + 1).sum())
        t0 = self._phase("select")
        toks, lps, arena, keys_d, _last_d, _pos_d, *counted = \
            self._enqueue(
                self._dispatch,
                self.params, jnp.asarray(last), self._pool.arena,
                self._tables(jnp.asarray(self._bt),
                             None if self._bt_w is None
                             else jnp.asarray(self._bt_w)),
                jnp.asarray(pos), jnp.asarray(keys))
        self._pool.arena = arena
        # every copy started before the first blocking read: one wait;
        # the last dispatch's clients run while the device works
        _start_host_copies(toks, lps, keys_d, *counted)
        self._wake_streams()
        toks, lps, keys = np.asarray(toks), np.asarray(lps), np.asarray(keys_d)
        self._fetched(self._dev_enq)
        if counted:  # ready with the tokens: the same program made them
            for name, n in zip(self._counters, np.asarray(counted[0])):
                self.stats[name] += int(n)
        # from the call until tokens and keys are on the host: the one
        # phase in which the device works for decoding
        self.invoke_stats.record(self._phase("dispatch") - t0)
        self.stats["slot_steps"] += self.B * self.K
        # a stream shed while a later one topped up has no tokens here
        run = [st for st in run if self._sstate.get(st["sid"]) is st]
        self._hand_over(run, [st["slot"] for st in run], toks, lps, self.K,
                        keys=keys)
        self._phase("emit")
        self.stats["dispatches"] += 1

    def _loop(self):
        """The engine loop. Dispatch → emit runs synchronously (the
        host state it re-uploads per block is a few hundred int32s —
        noise next to the gather the decode already pays), which keeps
        lane parking/rebinding and EDF preemption a plain host-side
        concern instead of a device-state pipeline hazard."""
        self._phase_t = _time.monotonic()
        self._iter_us = {}
        self._iter_starved = {}
        self._dev_seen, self._dev_busy_t = self._dev_enq, None
        while not self._stop_evt.is_set():
            self._end_iteration()
            admissions = self.stats["admissions"]
            busy = bool(self._sstate)
            for state in list(self._sstate.values()):
                if state["stream"].cancelled:
                    self._finish_paged(state, "cancelled")
            if self._held is not None and self._held.stream.cancelled:
                self._finish_stream(self._held.stream, "cancelled")
                self._held = None
            if busy:  # else these microseconds go to what comes next
                self._phase("other")
            progressed = False
            if self._partial is not None:
                if self._partial[0].stream.cancelled:
                    self._finish_stream(self._partial[0].stream, "cancelled")
                    self._partial = None
                else:
                    self._advance_partial()
                    progressed = True
            admitted = []
            # cold admissions whose prefill waits for the boundary's others
            cold: List[_ColdAdmission] = []
            while self._partial is None:
                if (self._spec is not None or self._lane_state
                        or self._window is not None) and \
                        len(self._sstate) + len(cold) >= self.B:
                    # a stream pinned to a lane, or a window arena sized
                    # for the lanes' windows: B at most
                    break
                if self._held is not None:
                    req, self._held = self._held, None
                else:
                    try:
                        req = self._pending.get_nowait()
                    except _queue.Empty:
                        break
                if req.stream.cancelled:
                    self._finish_stream(req.stream, "cancelled")
                    continue
                try:
                    if self.prefill_chunk is not None:
                        self._begin_partial_paged(req)
                        progressed = True
                        break
                    rec = self._admit_paged(req)
                except Exception as e:  # noqa: BLE001 — a bad request
                    # must not kill the engine loop
                    log.warning("serving: admit failed: %s", e)
                    self._finish_stream(req.stream, f"error: {e}")
                    continue
                if rec is None:
                    # pool can't cover this prompt yet: hold the head
                    # (completions free blocks; FIFO order preserved)
                    self.stats["kv_defers"] += 1
                    self._held = req
                    break
                if isinstance(rec, _ColdAdmission):
                    cold.append(rec)
                else:
                    admitted.append(rec)
                progressed = True
                # host work up to the enqueue of prefill, scatter and
                # first-token sample (of a cold admission that waits for
                # the boundary's others: up to its blocks)
                self._phase("admit", **req.who())
            for group, rows in self._prefill_groups(cold):
                try:
                    admitted.append(self._prefill_group(group, rows))
                except Exception as e:  # noqa: BLE001 — a bad program
                    # must not kill the engine loop
                    log.warning("serving: admit failed: %s", e)
                    for c in group:
                        self._finish_stream(c.req.stream, f"error: {e}")
                self._phase("admit", **group[0].req.who(), rows=len(group))
            for rec in admitted:  # start all fetches before blocking
                _start_host_copies(*rec[2:5])
            for rec in admitted:
                try:
                    self._activate_commit_paged(rec)
                except Exception as e:  # noqa: BLE001 — fail only these
                    # streams
                    log.warning("serving: activate failed: %s", e)
                    for req, state in zip(rec[0], rec[1]):
                        if self._sstate.get(state["sid"]) is state:
                            self._finish_paged(state, f"error: {e}")
                        else:
                            self._finish_stream(req.stream, f"error: {e}")
                # blocked on the prefill's result, then the first emit
                self._phase("first_token", **rec[0][0].who())
            if self.stats["admissions"] > admissions:
                self.stats["admit_boundaries"] += 1
            if len(self._sstate) > self.stats["concurrent_streams_max"]:
                self.stats["concurrent_streams_max"] = len(self._sstate)
            if not self._sstate:
                if not progressed:
                    self._wake.wait(timeout=0.05)
                    self._wake.clear()
                    self._phase("idle")
                continue
            try:
                if self._spec is not None:
                    self._spec_step_paged()
                else:
                    self._decode_step_paged()
            except Exception as e:  # noqa: BLE001 — a device failure
                # must not strand clients blocked on their streams
                self._recover(e)
