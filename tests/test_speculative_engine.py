"""Speculative decoding wired into the serving engine (speculate=K).

Drafts come from a shallow prefix slice of the target
(models/speculative.py); the target verifies every drafted position in
one chunk pass, so emitted tokens are exactly greedy-decode tokens —
speculation only changes how many positions a round advances, never the
values. That makes byte-parity with ``reference_greedy`` the whole
correctness story, whatever the arena's block size."""

import dataclasses

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from nnstreamer_tpu import parse_launch  # noqa: E402
from nnstreamer_tpu.serving import (  # noqa: E402
    ContinuousBatchingEngine,
    register_engine,
    unregister_engine,
)
from nnstreamer_tpu.models.transformer import init_params  # noqa: E402
from tests.test_serving import CFG, PARAMS, reference_greedy  # noqa: E402

PROMPTS = [[5, 11, 23, 42, 7], [4, 8, 15], [16, 23], [2, 2, 2, 2, 2]]


def spec_engine(**kw):
    kw.setdefault("max_streams", 2)
    kw.setdefault("steps_per_dispatch", 4)
    kw.setdefault("temperature", 0.0)
    kw.setdefault("speculate", 2)
    return ContinuousBatchingEngine(CFG, PARAMS, **kw)


@pytest.mark.parametrize("speculate", [1, 3, 5])
@pytest.mark.parametrize("block_tokens", [8, 16])
def test_speculative_greedy_parity(block_tokens, speculate):
    eng = spec_engine(block_tokens=block_tokens, speculate=speculate).start()
    try:
        assert eng._pool.block_tokens == block_tokens
        for p in PROMPTS:
            assert eng.generate(p, max_new_tokens=9, timeout=240) == \
                reference_greedy(p, 9), f"prompt={p}"
        streams = [eng.submit(p, max_new_tokens=9) for p in PROMPTS]
        conc = [s.result(timeout=240) for s in streams]
        assert eng.stats["spec_drafted"] > 0
        # at small scale the 1-layer draft tracks the 2-layer target
        # well; requiring SOME acceptance guards against a verifier
        # that silently rejects everything (== plain decode, hidden)
        assert eng.stats["spec_accepted"] > 0
    finally:
        eng.stop()
    for p, got in zip(PROMPTS, conc):
        assert got == reference_greedy(p, 9), f"prompt={p}"


def test_full_depth_draft_accepts_every_draft():
    """A draft as deep as the target proposes the target's own tokens:
    every draft is accepted, which runs the full-acceptance path (the
    draft cache's catch-up write of the last draft) round after round."""
    eng = spec_engine(speculate=4, speculate_layers=CFG.n_layers).start()
    try:
        got = [eng.generate(p, max_new_tokens=21, timeout=240)
               for p in PROMPTS[:2]]
    finally:
        eng.stop()
    assert got == [reference_greedy(p, 21) for p in PROMPTS[:2]]
    assert eng.stats["spec_drafted"] > 0
    assert eng.stats["spec_accepted"] == eng.stats["spec_drafted"]


def test_speculation_respects_the_cache_window():
    """A verify chunk writes positions pos .. pos+K: a prompt may take at
    most S - 1 - K tokens, and a stream's budget stops it before a round
    would write past the cache. Near the end it stays exact."""
    k = 3
    eng = spec_engine(speculate=k).start()
    try:
        with pytest.raises(ValueError, match="prompt length"):
            eng.submit(list(range(1, CFG.max_seq - k + 1)), max_new_tokens=4)
        longest = list(range(1, CFG.max_seq - k))       # S - 1 - K tokens
        assert len(eng.generate(longest, max_new_tokens=8,
                                timeout=240)) == 1
        prompt = [(i * 7 + 3) % CFG.vocab for i in range(56)]
        got = eng.generate(prompt, max_new_tokens=64, timeout=240)
    finally:
        eng.stop()
    assert len(got) == CFG.max_seq - len(prompt) - k      # the budget
    assert got == reference_greedy(prompt, len(got))


def test_moe_target_is_served_exactly():
    """A dense mixture-of-experts target and its depth-pruned draft: the
    verify chunk routes experts as one-at-a-time decoding does."""
    moe = dataclasses.replace(CFG, d_ff=64, num_experts=4)
    moe_params = init_params(moe, seed=9)
    eng = ContinuousBatchingEngine(moe, moe_params, max_streams=2,
                                   steps_per_dispatch=4, temperature=0.0,
                                   speculate=3).start()
    try:
        got = [eng.generate(p, max_new_tokens=15, timeout=240)
               for p in PROMPTS[:3]]
    finally:
        eng.stop()
    assert got == [reference_greedy(p, 15, cfg=moe, params=moe_params)
                   for p in PROMPTS[:3]]
    assert eng.stats["spec_drafted"] > 0


def test_speculate_requires_greedy():
    with pytest.raises(ValueError, match="greedy"):
        spec_engine(temperature=0.8)


def test_draft_deeper_than_the_target_is_refused():
    with pytest.raises(ValueError, match="n_layers"):
        spec_engine(speculate_layers=CFG.n_layers + 1)


def test_set_speculate_guards():
    eng = ContinuousBatchingEngine(
        CFG, PARAMS, max_streams=2, temperature=0.0)
    with pytest.raises(ValueError):
        eng.set_speculate(-1)
    with pytest.raises(ValueError):
        eng.set_speculate(CFG.max_seq)
    eng.start()
    try:
        with pytest.raises(RuntimeError, match="stopped"):
            eng.set_speculate(3)
    finally:
        eng.stop()


def test_lm_serve_speculate_property_configures_engine():
    """tensor_lm_serve speculate=K reaches through to the engine at
    element start — the pipeline string is the opt-in surface."""
    engine = ContinuousBatchingEngine(
        CFG, PARAMS, max_streams=2, steps_per_dispatch=4,
        temperature=0.0)
    register_engine("lm_spec", engine)
    server = parse_launch(
        "tensor_query_serversrc name=ssrc port=0 ! "
        "tensor_lm_serve engine=lm_spec max-new-tokens=4 "
        "speculate=2 speculate-layers=1 name=serve ! "
        "tensor_query_serversink")
    try:
        server.start()
        assert engine.speculate == 2
        assert engine._speculate_layers == 1
    finally:
        server.stop()
        unregister_engine("lm_spec")
