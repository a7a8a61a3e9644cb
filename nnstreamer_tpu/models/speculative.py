"""Speculative decoding — draft-and-verify generation in one program.

New capability beyond the reference (no LM machinery in-tree; its closest
idea is pipelined stages hiding latency behind throughput). On a TPU the
single-token decode step is dispatch- and bandwidth-bound: each step is a
[1, d_model]×weights pass that leaves the MXU idle. Speculative decoding
converts γ sequential target-model steps into

  1. γ cheap draft-model steps (``lax.scan`` inside the program), then
  2. ONE target-model *chunk* pass over the γ+1 candidate positions
     (``build_chunk_decode`` — a [γ+1, d_model] matmul per layer), then
  3. a vectorized accept/reject — no Python control flow.

Greedy acceptance: the emitted stream is IDENTICAL to target-only greedy
decode (tested token-for-token in tests/test_speculative.py); speculation
changes the schedule, never the output.

**Rewind-free cache contract.** A rejected suffix needs no cache
cleanup: both models write slot i before any query attends it (the
``slot <= pos`` mask admits slot i only once pos reaches i, and the
write happens earlier in the same step), so stale kv beyond the accepted
prefix is unreachable and is overwritten when generation gets there.
Resetting ``pos`` to the accept point IS the rewind.

The whole round — draft loop, verify, accept — is one jitted function
with both caches donated; the host only reads the [γ+1] emitted-token
row and the accept count.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from nnstreamer_tpu.models.transformer import (
    TransformerConfig,
    build_chunk_decode,
    build_decode_step,
    build_prefill,
    init_cache,
)


def build_speculative_round(target_cfg: TransformerConfig,
                            draft_cfg: TransformerConfig,
                            gamma: int = 4,
                            max_seq: Optional[int] = None) -> Callable:
    """Returns ``round(target_params, draft_params, last_tok[int32 b],
    target_cache, draft_cache, pos[int32 scalar]) -> (tokens[b, γ+1],
    n_emit[int32 scalar], target_cache, draft_cache, new_pos)``.

    ``tokens[:, :n_emit]`` are the round's emitted ids (greedy-exact
    w.r.t. the target model); ``n_emit`` ∈ [1, γ+1] — γ accepted drafts
    plus the target's bonus token, or the accepted prefix plus the
    target's correction. Entries past ``n_emit`` are the speculative
    garbage the caller must ignore.

    Batch must be 1 (checked at trace time): the accept decision is a
    single prefix length, and rows with different acceptance would need
    per-row positions through the chunk verify. Run independent
    SpeculativeDecoder instances (or the serving engine) for parallel
    streams.

    Vocabularies must match; the draft is typically 4-10x smaller.
    """
    if target_cfg.vocab != draft_cfg.vocab:
        raise ValueError(
            f"speculative: target vocab {target_cfg.vocab} != draft vocab "
            f"{draft_cfg.vocab}")
    if gamma < 1:
        raise ValueError(f"speculative: gamma must be >= 1, got {gamma}")
    s_max = max_seq or target_cfg.max_seq
    draft_step = build_decode_step(draft_cfg, s_max)
    target_chunk = build_chunk_decode(target_cfg, s_max)

    def spec_round(target_params, draft_params, last_tok, target_cache,
                   draft_cache, pos):
        if last_tok.shape[0] != 1:
            raise ValueError(
                f"speculative: batch must be 1 (got {last_tok.shape[0]}) "
                "— the accept prefix is a single length; run one decoder "
                "per stream")
        pos = jnp.asarray(pos, jnp.int32)

        def draft_body(carry, _):
            tok, cache, dpos = carry
            logits, cache = draft_step(draft_params, tok, cache, dpos)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (nxt, cache, dpos + 1), nxt

        (d_last, draft_cache, d_pos), drafts = jax.lax.scan(
            draft_body, (last_tok, draft_cache, pos), None, length=gamma)
        drafts = jnp.transpose(drafts)                     # [b, γ]
        # the scan wrote kv for [last, d_1..d_{γ-1}] at slots pos..pos+γ-1
        # but NOT d_γ's: on full acceptance the next round starts past
        # slot pos+γ, whose kv must be d_γ's — one extra cache-write step
        # (logits discarded) closes the hole
        _, draft_cache = draft_step(draft_params, d_last, draft_cache,
                                    d_pos)

        # target scores positions pos..pos+γ in one chunk pass over
        # [last_tok, d_1..d_γ]; logits[:, i] predicts position pos+i+1
        chunk_toks = jnp.concatenate([last_tok[:, None], drafts], axis=1)
        logits, target_cache = target_chunk(
            target_params, chunk_toks, target_cache, pos)
        target_toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)

        # longest prefix where every draft matches the target's choice
        # (b == 1, enforced above)
        match = drafts[0] == target_toks[0, :gamma]        # [γ]
        n_acc = jnp.argmin(jnp.concatenate(
            [match, jnp.asarray([False])]).astype(jnp.int32))
        # emitted: d_1..d_n  then the target token at position n (the
        # correction on mismatch, the bonus token on full acceptance)
        out = jnp.where(jnp.arange(gamma + 1) < n_acc,
                        jnp.concatenate(
                            [drafts, drafts[:, -1:]], axis=1),
                        jnp.take_along_axis(
                            target_toks,
                            jnp.minimum(n_acc, gamma)[None, None] *
                            jnp.ones((drafts.shape[0], gamma + 1),
                                     jnp.int32),
                            axis=1))
        n_emit = n_acc + 1
        return out, n_emit, target_cache, draft_cache, pos + n_emit

    return spec_round


def build_speculative_dispatch(target_cfg: TransformerConfig,
                               draft_cfg: TransformerConfig,
                               gamma: int = 4,
                               rounds: int = 8,
                               max_seq: Optional[int] = None) -> Callable:
    """R speculative rounds in ONE program: ``dispatch(tp, dp,
    last_tok[b], t_cache, d_cache, pos) -> (buf[b, R*(γ+1)],
    n_emits[R], last_tok, t_cache, d_cache, pos)``.

    Emitted tokens append into a device-side buffer (each round's
    ``dynamic_update_slice`` at the running count overwrites the previous
    round's speculative tail), so the host pays ONE sync per R rounds
    instead of one per round, exactly like the serving engine's [B, K]
    block dispatch (serving/engine.py). ``buf[:, :sum(n_emits)]`` is
    valid; a round that would write past the cache window is skipped
    (``lax.cond``) and reports ``n_emit = 0``.
    """
    spec_round = build_speculative_round(target_cfg, draft_cfg, gamma,
                                         max_seq)
    s_max = max_seq or target_cfg.max_seq
    width = gamma + 1

    def dispatch(target_params, draft_params, last_tok, t_cache, d_cache,
                 pos):
        b = last_tok.shape[0]
        buf = jnp.zeros((b, rounds * width), jnp.int32)

        def body(carry, _):
            last, t_cache, d_cache, pos, buf, count = carry

            def run(op):
                last, t_cache, d_cache, pos, buf, count = op
                toks, n_emit, t_cache, d_cache, pos = spec_round(
                    target_params, draft_params, last, t_cache, d_cache,
                    pos)
                buf = jax.lax.dynamic_update_slice(buf, toks, (0, count))
                last = jnp.take_along_axis(
                    toks, (n_emit - 1) * jnp.ones((b, 1), jnp.int32),
                    axis=1)[:, 0]
                return (last, t_cache, d_cache, pos, buf,
                        count + n_emit), n_emit

            def skip(op):
                return op, jnp.asarray(0, jnp.int32)

            carry, n_emit = jax.lax.cond(
                pos + gamma < s_max, run, skip,
                (last, t_cache, d_cache, pos, buf, count))
            return carry, n_emit

        (last_tok, t_cache, d_cache, pos, buf, _), n_emits = jax.lax.scan(
            body,
            (last_tok, t_cache, d_cache, pos, buf,
             jnp.asarray(0, jnp.int32)),
            None, length=rounds)
        return buf, n_emits, last_tok, t_cache, d_cache, pos

    return dispatch


def build_speculative_generate(target_cfg: TransformerConfig,
                               draft_cfg: TransformerConfig,
                               gamma: int,
                               max_new: int,
                               max_seq: Optional[int] = None) -> Callable:
    """A WHOLE greedy generation as one program: ``gen(tp, dp,
    last_tok[b], t_cache, d_cache, pos) -> (buf[b, max_new+γ], count)``.

    ``lax.while_loop`` drives speculative rounds until ``count >=
    max_new`` or the cache window ends — the host pays ONE sync for the
    entire generation, matching the fully-async profile of the repo-loop
    decode pipeline (bench ``decode``). ``buf[:, :min(count, max_new)]``
    is the output; the returned ``count`` is packed as
    ``[count, rounds]`` so acceptance stats survive the fusion. One
    executable per distinct ``max_new``.
    """
    spec_round = build_speculative_round(target_cfg, draft_cfg, gamma,
                                         max_seq)
    s_max = max_seq or target_cfg.max_seq
    width = max_new + gamma  # last round may overshoot by ≤ γ

    def gen(target_params, draft_params, last_tok, t_cache, d_cache, pos):
        b = last_tok.shape[0]
        buf = jnp.zeros((b, width), jnp.int32)

        def cond(carry):
            _, _, _, pos, _, count, _ = carry
            return jnp.logical_and(count < max_new, pos + gamma < s_max)

        def body(carry):
            last, t_cache, d_cache, pos, buf, count, rounds = carry
            toks, n_emit, t_cache, d_cache, pos = spec_round(
                target_params, draft_params, last, t_cache, d_cache, pos)
            buf = jax.lax.dynamic_update_slice(buf, toks, (0, count))
            last = jnp.take_along_axis(
                toks, (n_emit - 1) * jnp.ones((b, 1), jnp.int32),
                axis=1)[:, 0]
            return (last, t_cache, d_cache, pos, buf, count + n_emit,
                    rounds + 1)

        (_, t_cache, d_cache, pos, buf, count, rounds) = jax.lax.while_loop(
            cond, body,
            (last_tok, t_cache, d_cache, pos, buf,
             jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32)))
        return buf, jnp.stack([count, rounds])

    return gen


class SpeculativeDecoder:
    """Host-side generation loop around the jitted multi-round dispatch.

    One target + one draft model, greedy, batch 1. The draft cache rides
    along; the host reads one ``[R*(γ+1)]`` token buffer per dispatch —
    or, with ``fused=True``, runs the whole generation in one program
    and reads a single buffer (no mid-generation host syncs at all).
    """

    def __init__(self, target_cfg: TransformerConfig, target_params: Any,
                 draft_cfg: TransformerConfig, draft_params: Any,
                 gamma: int = 4, rounds_per_dispatch: int = 4,
                 max_seq: Optional[int] = None):
        self.tc, self.tp = target_cfg, target_params
        self.dc, self.dp = draft_cfg, draft_params
        self.gamma = int(gamma)
        self.R = int(rounds_per_dispatch)
        self.S = int(max_seq or target_cfg.max_seq)
        self._dispatch = jax.jit(
            build_speculative_dispatch(target_cfg, draft_cfg, self.gamma,
                                       self.R, self.S),
            donate_argnums=(3, 4))
        self._prefill_t = jax.jit(build_prefill(target_cfg, self.S))
        self._prefill_d = jax.jit(build_prefill(draft_cfg, self.S))
        self._fused: dict = {}  # max_new → jitted whole-generation program
        self.stats = {"rounds": 0, "tokens": 0, "dispatches": 0}

    def generate(self, prompt, max_new_tokens: int = 64,
                 fused: bool = False) -> list:
        """Greedy generation; output is token-identical to target-only
        greedy decode. ``fused=True`` runs the whole generation as one
        program (single host sync; one compile per max_new_tokens value)
        — fastest when tokens aren't consumed mid-stream."""
        prompt = np.asarray(prompt, np.int32).reshape(1, -1)
        n = prompt.shape[1]
        if not 0 < n < self.S:
            raise ValueError(f"speculative: prompt length {n} must be in "
                             f"(0, {self.S})")
        t_logits, t_cache = self._prefill_t(self.tp, jnp.asarray(prompt))
        _, d_cache = self._prefill_d(self.dp, jnp.asarray(prompt))
        first = int(jnp.argmax(t_logits[0]))
        out = [first]
        last = jnp.asarray([first], jnp.int32)
        pos = jnp.asarray(n, jnp.int32)
        if fused:
            m = max_new_tokens - 1  # minus the prefill-seeded first token
            if m > 0:
                if m not in self._fused:
                    # no donation: the fused program's outputs contain no
                    # cache-shaped array for the inputs to alias with
                    self._fused[m] = jax.jit(build_speculative_generate(
                        self.tc, self.dc, self.gamma, m, self.S))
                buf, count_rounds = self._fused[m](self.tp, self.dp, last,
                                                   t_cache, d_cache, pos)
                # both transfers in flight before either blocks (one
                # host↔device round trip instead of two)
                for arr in (buf, count_rounds):
                    arr.copy_to_host_async()
                count, rounds = (int(x) for x in np.asarray(count_rounds))
                out.extend(np.asarray(buf)[0, :count].tolist())
                self.stats["dispatches"] += 1
                self.stats["tokens"] += count
                self.stats["rounds"] += rounds
            return out[:max_new_tokens]
        while len(out) < max_new_tokens:
            buf, n_emits, last, t_cache, d_cache, pos = self._dispatch(
                self.tp, self.dp, last, t_cache, d_cache, pos)
            for arr in (buf, n_emits):
                arr.copy_to_host_async()
            n_emits = np.asarray(n_emits)
            count = int(n_emits.sum())
            if count == 0:
                break  # cache window exhausted — every round skipped
            out.extend(np.asarray(buf)[0, :count].tolist())
            self.stats["dispatches"] += 1
            self.stats["rounds"] += int((n_emits > 0).sum())
            self.stats["tokens"] += count
        return out[:max_new_tokens]

    @property
    def mean_accepted(self) -> float:
        """Average tokens emitted per executed round (1.0 = no
        speculation win; γ+1 = every draft accepted)."""
        return self.stats["tokens"] / max(1, self.stats["rounds"])


def draft_from_target(cfg: TransformerConfig, params: Any,
                      n_layers: int) -> Tuple[TransformerConfig, Any]:
    """Depth-pruned self-speculative draft: the target's FIRST
    ``n_layers`` layers (params are stacked [L, ...], so the draft is a
    zero-copy slice) sharing the embedding — no separately-trained draft
    model needed, and early layers correlate strongly with the full
    model's prediction, which is what acceptance length depends on.
    """
    if not 0 < n_layers <= cfg.n_layers:
        raise ValueError(
            f"draft_from_target: n_layers must be in (0, {cfg.n_layers}], "
            f"got {n_layers}")
    draft_cfg = dataclasses.replace(cfg, n_layers=n_layers)
    draft_params = {
        k: (v if k in ("embed", "ln_f") else v[:n_layers])
        for k, v in params.items()
    }
    return draft_cfg, draft_params
