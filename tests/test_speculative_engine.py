"""Speculative decoding wired into the serving engine (speculate=K).

Drafts come from a shallow prefix slice of the target
(models/speculative.py); the target verifies every drafted position in
one chunk pass, so emitted tokens are exactly greedy-decode tokens —
speculation only changes how many positions a round advances, never the
values. That makes byte-parity with ``reference_greedy`` the whole
correctness story, whatever the arena's block size."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from nnstreamer_tpu import parse_launch  # noqa: E402
from nnstreamer_tpu.serving import (  # noqa: E402
    ContinuousBatchingEngine,
    register_engine,
    unregister_engine,
)
from tests.test_serving import CFG, PARAMS, reference_greedy  # noqa: E402

PROMPTS = [[5, 11, 23, 42, 7], [4, 8, 15], [16, 23], [2, 2, 2, 2, 2]]


def spec_engine(**kw):
    kw.setdefault("max_streams", 2)
    kw.setdefault("steps_per_dispatch", 4)
    kw.setdefault("temperature", 0.0)
    kw.setdefault("speculate", 2)
    return ContinuousBatchingEngine(CFG, PARAMS, **kw)


@pytest.mark.parametrize("block_tokens", [8, 16])
def test_speculative_greedy_parity(block_tokens):
    eng = spec_engine(block_tokens=block_tokens).start()
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


def test_speculate_requires_greedy():
    with pytest.raises(ValueError, match="greedy"):
        spec_engine(temperature=0.8)


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
