"""Paged decode attention (ops/paged_attention.py).

The kernel reads each lane's live blocks where they lie in the arena; the
gather form copies every lane's whole table out and attends under a mask.
Off a TPU the kernel runs through the Pallas interpreter when forced, which
is how these tests hold it to the gather form: the same numbers
(``_attend_cache``'s contract: only the order of the sums differs), in a
call of its own and inside the engine's K-step decode dispatch. What
``auto`` builds is the gather form wherever the kernel does not run: off a
TPU, under a mesh, for an int8 arena and for the chunk builder. One test
compiles the kernel at the three cells' widths for a described TPU v5e:
head dims 128 and 256, groups of 1, 4 and 8 query heads a key-value head,
each arena in the order its codec makes it (heads-major under 8 key-value
heads); another the three other programs that hold a two-head arena, none
of which may move it; another the routed experts' kernel at both expert
cells' shapes.
"""

import functools
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

import nnstreamer_tpu.ops as ops_pkg  # noqa: E402
from nnstreamer_tpu.models.transformer import (  # noqa: E402
    TransformerConfig,
    _attend_cache,
    _paged_gather,
    build_paged_chunk,
    init_params,
)
from nnstreamer_tpu.ops.paged_attention import (  # noqa: E402
    _paged_decode,
    paged_attention,
    paged_attention_form,
)
from nnstreamer_tpu.serving import ContinuousBatchingEngine  # noqa: E402
from nnstreamer_tpu.serving import engine as engine_mod  # noqa: E402

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")

T, MB, DH, LAYERS = 8, 12, 128, 2
#: tokens held by each lane: the edges of a block, mid-table, one short of
#: the table, and an EMPTY lane (its table all sentinel)
HELD = (1, T - 1, T, T + 1, (MB * T) // 2 + 3, MB * T - 1, 0)


def _pool(hk, dtype, seed, dh=DH, t=T, heads_major=False):
    """A scrambled pool: every lane's blocks anywhere in the arena, the
    zero block last, unallocated table entries at the sentinel. Blocks of
    ``t`` tokens (``HELD`` scaled with it); ``heads_major`` hands the SAME
    numbers out as ``[.., hk, t, dh]`` blocks."""
    rng = np.random.default_rng(seed)
    lanes = len(HELD)
    nb = lanes * MB
    pages = rng.standard_normal((LAYERS, nb + 1, 2, t, hk, dh))
    pages[:, nb] = 0.0
    if heads_major:
        pages = np.swapaxes(pages, 3, 4)
    order = rng.permutation(nb).reshape(lanes, MB)
    bt = np.full((lanes, MB), nb + 1, np.int32)
    held = [h * t // T for h in HELD]
    for lane, n in enumerate(held):
        bt[lane, :-(-n // t)] = order[lane, :-(-n // t)]
    pos = np.maximum(np.asarray(held) - 1, 0).astype(np.int32)
    return (jnp.asarray(pages, dtype), jnp.asarray(bt), jnp.asarray(pos),
            rng)


def _gather_form(q, pages, layer, bt, pos, scale):
    g = _paged_gather(pages, layer, bt)
    mask = jnp.arange(MB * T)[None, None, None, :] <= pos[:, None, None,
                                                          None]
    return _attend_cache(q, g[:, 0], g[:, 1], mask, q.shape[-1], q.dtype,
                         scale=scale)


@pytest.mark.parametrize("chunk", [2, 8], ids=["chunk2", "chunk8"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("hq,hk,scale,dh", [
    (16, 16, None, 128), (32, 8, 0.3, 128), (16, 2, 0.0625, 256)],
    ids=["16x16", "32over8", "16over2_dh256"])
def test_kernel_equals_attend_cache_over_the_gathered_table(
        hq, hk, scale, dh, dtype, tol, chunk):
    pages, bt, pos, rng = _pool(hk, dtype, seed=hq + chunk, dh=dh)
    q = jnp.asarray(rng.standard_normal((len(HELD), 1, hq, dh)), dtype)
    before = np.asarray(pages, np.float32)
    for layer in range(LAYERS):
        want = _gather_form(q, pages, layer, bt, pos, scale)
        got = paged_attention(q, pages, layer, bt, pos, scale=scale,
                              force="pallas", chunk_blocks=chunk)
        assert got.shape == want.shape and got.dtype == want.dtype
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)
        # the empty lane read the zero block: finite, and exactly zero
        assert not np.asarray(got, np.float32)[-1].any()
    after = np.asarray(pages, np.float32)
    np.testing.assert_array_equal(after, before)     # the arena untouched
    assert not after[:, -1].any()                    # the zero block zero


@pytest.mark.parametrize("dh", [128, 256])
@pytest.mark.parametrize("hq,hk,dtype,tol", [
    (16, 1, jnp.float32, 1e-5), (16, 1, jnp.bfloat16, 2e-2),
    (16, 2, jnp.float32, 1e-5), (16, 2, jnp.bfloat16, 2e-2),
    (16, 4, jnp.float32, 1e-5), (16, 4, jnp.bfloat16, 2e-2),
    (8, 2, jnp.float32, 1e-5)],    # 8 rows: a float32 tile, half a bf16 one
    ids=["16over1-f32", "16over1-bf16", "16over2-f32", "16over2-bf16",
         "16over4-f32", "16over4-bf16", "8over2-f32"])
def test_heads_major_kernel_equals_the_reference_and_the_gather_form(
        hq, hk, dtype, tol, dh):
    """Blocks of ``[hk, T, dh]`` rows (fewer key-value heads than a tile
    has rows): the kernel's two masks find a column's head and slot in
    that order; ragged positions, a block edge, a full table, an empty
    lane whose table is all sentinel. Held to ``paged_attention_reference``
    told the same order, which is ``_attend_cache`` over what
    ``_paged_gather`` copies out, which equals, to the bit, the gather form
    over the token-major arena of the same numbers."""
    from nnstreamer_tpu.ops.paged_attention import paged_attention_reference

    seed = hq + hk + dh
    t = 16
    pages, bt, pos, rng = _pool(hk, dtype, seed, dh=dh, t=t,
                                heads_major=True)
    twin = _pool(hk, dtype, seed, dh=dh, t=t)[0]
    assert pages.shape[3:5] == (hk, t) and twin.shape[3:5] == (t, hk)
    q = jnp.asarray(rng.standard_normal((len(HELD), 1, hq, dh)), dtype)
    before = np.asarray(pages, np.float32)
    for layer in range(LAYERS):
        g = _paged_gather(pages, layer, bt, True)
        np.testing.assert_array_equal(np.asarray(g, np.float32), np.asarray(
            _paged_gather(twin, layer, bt), np.float32))
        mask = jnp.arange(MB * t)[None, None, None, :] \
            <= pos[:, None, None, None]
        want = _attend_cache(q, g[:, 0], g[:, 1], mask, dh, dtype, scale=0.1)
        ref = paged_attention_reference(q, pages, layer, bt, pos, 0.1,
                                        heads_major=True)
        np.testing.assert_array_equal(np.asarray(ref, np.float32),
                                      np.asarray(want, np.float32))
        for chunk in (2, 8):
            got = paged_attention(q, pages, layer, bt, pos, scale=0.1,
                                  force="pallas", chunk_blocks=chunk,
                                  heads_major=True)
            assert got.shape == want.shape and got.dtype == want.dtype
            np.testing.assert_allclose(np.asarray(got, np.float32),
                                       np.asarray(want, np.float32),
                                       rtol=tol, atol=tol)
            assert not np.asarray(got, np.float32)[-1].any()  # empty lane
    np.testing.assert_array_equal(np.asarray(pages, np.float32), before)


def test_the_order_is_told_never_read_off_the_shape():
    """16 tokens of 16 heads and 16 heads of 16 tokens are one shape: the
    same arena read in the two orders gives different numbers, each the
    gather form's for the order it was told."""
    pages, bt, pos, rng = _pool(16, jnp.float32, seed=5, t=16)
    q = jnp.asarray(rng.standard_normal((len(HELD), 1, 16, DH)), jnp.float32)
    out = {}
    for heads_major in (False, True):
        g = _paged_gather(pages, 0, bt, heads_major)
        mask = jnp.arange(MB * 16)[None, None, None, :] \
            <= pos[:, None, None, None]
        want = _attend_cache(q, g[:, 0], g[:, 1], mask, DH, jnp.float32)
        out[heads_major] = got = paged_attention(
            q, pages, 0, bt, pos, force="pallas", heads_major=heads_major)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
    assert np.abs(np.asarray(out[True]) - np.asarray(out[False])).max() > 0.1


def test_layer_may_be_traced_and_reference_is_the_gather_form():
    pages, bt, pos, rng = _pool(16, jnp.float32, seed=3)
    q = jnp.asarray(rng.standard_normal((len(HELD), 1, 16, DH)),
                    jnp.float32)

    @jax.jit
    def both(layer):
        return (paged_attention(q, pages, layer, bt, pos, force="pallas"),
                paged_attention(q, pages, layer, bt, pos,
                                force="reference"))

    for layer in range(LAYERS):
        got, ref = both(jnp.int32(layer))
        np.testing.assert_array_equal(
            np.asarray(ref),
            np.asarray(_gather_form(q, pages, layer, bt, pos, None)))
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("why,kw", [
    ("head dim", dict(dh=64)),
    ("query tokens", dict(queries=2)),
    ("sublanes", dict(hq=4, hk=4)),
    ("key-value heads", dict(hq=24, hk=16)),
    ("keys and values", dict(q_dtype=jnp.float32)),
    # heads-major: 8 tokens of one head are half a bfloat16 tile; 3 heads
    # do not divide 16 (read token-major the same arena is 8 heads: taken)
    ("sublanes", dict(hk=1, heads_major=True)),
    ("key-value heads", dict(pages=(1, 5, 2, 3, 8, 128), heads_major=True)),
])
def test_forced_kernel_refuses_shapes_it_does_not_take(why, kw):
    hq, hk, dh = kw.get("hq", 16), kw.get("hk", 16), kw.get("dh", 128)
    hm = kw.get("heads_major", False)
    q = jnp.zeros((2, kw.get("queries", 1), hq, dh),
                  kw.get("q_dtype", jnp.bfloat16))
    pages = jnp.zeros(kw.get("pages", (1, 5, 2, hk, T, dh) if hm
                             else (1, 5, 2, T, hk, dh)), jnp.bfloat16)
    bt = jnp.zeros((2, 2), jnp.int32)
    pos = jnp.zeros((2,), jnp.int32)
    with pytest.raises(ValueError, match=why):
        paged_attention(q, pages, 0, bt, pos, force="pallas", heads_major=hm)
    # auto gives way to the gather form instead (off a TPU it always does)
    assert paged_attention_form(q, pages, bt, heads_major=hm) == "gather"


# -- a latent arena: one row a token, shared by every head --------------------

def _latent_pool(dtype, seed, width=192, t=T):
    rng = np.random.default_rng(seed)
    lanes = len(HELD)
    nb = lanes * MB
    pages = rng.standard_normal((LAYERS, nb + 1, 1, t, width))
    pages[:, nb] = 0.0
    order = rng.permutation(nb).reshape(lanes, MB)
    bt = np.full((lanes, MB), nb + 1, np.int32)
    held = [h * t // T for h in HELD]
    for lane, n in enumerate(held):
        bt[lane, :-(-n // t)] = order[lane, :-(-n // t)]
    pos = np.maximum(np.asarray(held) - 1, 0).astype(np.int32)
    return (jnp.asarray(pages, dtype), jnp.asarray(bt), jnp.asarray(pos),
            rng)


@pytest.mark.parametrize("chunk", [2, 5, None], ids=["chunk2", "chunk5",
                                                     "default"])
@pytest.mark.parametrize("dtype,tol,t", [(jnp.float32, 1e-5, 8),
                                         (jnp.bfloat16, 2e-2, 16)],
                         ids=["f32", "bf16"])
def test_latent_kernel_equals_attend_cache_over_the_gathered_rows(
        dtype, tol, t, chunk):
    """K is a block's rows at all their columns, V the first 128 columns
    of the same rows, 16 query heads against the one shared row: the
    kernel against the gather form, and the gather form against
    ``_attend_cache`` by hand."""
    width, vw, hq = 192, 128, 16
    pages, bt, pos, rng = _latent_pool(dtype, seed=7, width=width, t=t)
    q = jnp.asarray(rng.standard_normal((len(HELD), 1, hq, width)), dtype)
    for layer in range(LAYERS):
        g = _paged_gather(pages, layer, bt)[:, 0]             # [b, S, w]
        mask = jnp.arange(MB * t)[None, None, None, :] \
            <= pos[:, None, None, None]
        want = _attend_cache(q, g[:, :, None], g[:, :, None, :vw], mask,
                             width, dtype, scale=0.2)
        ref = paged_attention(q, pages, layer, bt, pos, scale=0.2,
                              force="reference", v_width=vw)
        np.testing.assert_array_equal(np.asarray(ref, np.float32),
                                      np.asarray(want, np.float32))
        got = paged_attention(q, pages, layer, bt, pos, scale=0.2,
                              force="pallas", chunk_blocks=chunk, v_width=vw)
        assert got.shape == (len(HELD), 1, hq, vw) and got.dtype == dtype
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)
        assert not np.asarray(got, np.float32)[-1].any()   # the empty lane


@pytest.mark.parametrize("why,kw", [
    ("not one", dict(pages=(1, 5, 2, 16, 1, 192))),
    ("value width", dict(v_width=96)),
    ("value width", dict(v_width=192)),
    ("against rows of", dict(q_width=160)),
    ("sublanes", dict(hq=4)),
    ("sublanes", dict(pages=(1, 5, 1, 8, 192))),
    ("no heads", dict(heads_major=True)),
])
def test_forced_latent_kernel_refuses_shapes_it_does_not_take(why, kw):
    q = jnp.zeros((2, 1, kw.get("hq", 16), kw.get("q_width", 192)),
                  jnp.bfloat16)
    pages = jnp.zeros(kw.get("pages", (1, 5, 1, 16, 192)), jnp.bfloat16)
    bt = jnp.zeros((2, 2), jnp.int32)
    vw, hm = kw.get("v_width", 128), kw.get("heads_major", False)
    with pytest.raises(ValueError, match=why):
        paged_attention(q, pages, 0, bt, jnp.zeros((2,), jnp.int32),
                        force="pallas", v_width=vw, heads_major=hm)
    assert paged_attention_form(q, pages, bt, v_width=vw,
                                heads_major=hm) == "gather"


# -- inside the engine's K-step dispatch --------------------------------------

CFG = TransformerConfig(vocab=256, d_model=1024, n_heads=8, n_layers=2,
                        d_ff=128, max_seq=64, dtype=jnp.float32)
PARAMS = init_params(CFG, seed=2)
PROMPTS = [[5, 11, 23, 42, 7, 9, 9, 1, 30], [4, 8, 15], [16] * 17]


def _serve(**kw):
    eng = ContinuousBatchingEngine(
        CFG, PARAMS, max_streams=3, steps_per_dispatch=4, temperature=0.0,
        block_tokens=T, **kw).start()
    try:
        streams = [eng.submit(p, max_new_tokens=11) for p in PROMPTS]
        return eng, [(s.result(timeout=300), list(s.logprobs))
                     for s in streams]
    finally:
        eng.stop()


def test_k_step_dispatch_with_the_kernel_serves_the_gather_forms_tokens(
        monkeypatch):
    """Eight heads, so a token-major arena (a dense block's query heads
    ARE its key-value heads, and the kernel wants eight: the heads-major
    twin of this test is ``tests/test_qwen3_next_lm.py``'s, 8 query over
    2 key-value heads)."""
    _, want = _serve(attention="reference")
    # off a TPU auto builds the gather form, so hand the engine the kernel
    # forced (the interpreter runs it inside the real K-step program)
    monkeypatch.setattr(ops_pkg, "paged_attention", functools.partial(
        paged_attention, force="pallas"))
    eng, got = _serve()
    assert not eng._pool.heads_major
    text = engine_mod.decode_program_text(eng.obs_name)
    assert "kv_gather/gather" not in text and "/attend/" in text
    for (toks, lps), (ref_toks, ref_lps) in zip(got, want):
        assert toks == ref_toks
        np.testing.assert_allclose(lps, ref_lps, atol=1e-4)


# -- what auto builds where the kernel does not run ---------------------------

def _engine(**kw):
    kw.setdefault("block_tokens", T)
    return ContinuousBatchingEngine(
        CFG, PARAMS, max_streams=2, steps_per_dispatch=2, temperature=0.0,
        **kw)


@pytest.mark.parametrize("case", ["cpu", "mesh", "int8", "reference"])
def test_auto_builds_the_gather_form_where_the_kernel_does_not_run(case):
    kw = {}
    if case == "mesh":
        from nnstreamer_tpu.parallel.mesh import make_mesh

        kw["mesh"] = make_mesh([("dp", 2)])
    elif case == "int8":
        kw["kv_quant"] = "int8"
    elif case == "reference":
        kw["attention"] = "reference"
    eng = _engine(**kw)
    assert eng.decode_attention == "gather"
    text = engine_mod.decode_program_text(eng.obs_name)
    assert "kv_gather/gather" in text
    assert "nns_paged_decode" not in text


def test_chunk_builder_keeps_the_gather_form():
    arena = jnp.zeros((CFG.n_layers, 9, 2, T, CFG.n_heads, CFG.head_dim),
                      CFG.dtype)
    text = jax.jit(build_paged_chunk(CFG, T)).lower(
        PARAMS, jnp.zeros((2, 4), jnp.int32), arena,
        jnp.zeros((2, CFG.max_seq // T), jnp.int32),
        jnp.zeros((2,), jnp.int32), jnp.full((2,), 4, jnp.int32)
    ).as_text(debug_info=True)
    assert "gather" in text and "nns_paged_decode" not in text


def test_block_counters_count_what_the_positions_say():
    eng = _engine().start()
    try:
        assert eng.stats["kv_blocks_live"] == 0 == \
            eng.stats["kv_blocks_table"]
        eng.generate([3] * 13, max_new_tokens=5, timeout=120)
    finally:
        eng.stop()
    # the first token comes from the prefill; two dispatches of K = 2 make
    # the other four at positions 13..16 while the second lane stays empty
    # (its positions 0, 1 are one block each)
    assert eng.stats["dispatches"] == 2
    live = sum(p // T + 1 for p in (13, 14, 15, 16)) + 4 * 1
    assert eng.stats["kv_blocks_live"] == live
    assert eng.stats["kv_blocks_table"] == 2 * 2 * eng.B * eng.MB
    assert isinstance(eng.stats["kv_blocks_live"], int)


# -- the kernel at the cells' widths, compiled for the chip -------------------

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _moves_of(text, elements):
    """The instructions of a compiled program that MOVE ``elements`` or
    more: a copy, a reshape that is no bitcast, a transpose (alone or as
    the root a fusion is named after)."""

    moves = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]*)\]", line)
        if not m or not m.group(2):
            continue
        size = int(np.prod([int(d) for d in m.group(2).split(",")]))
        if size >= elements and (
                re.search(r"[\]}] (copy|reshape|transpose)\(", line)
                or (" fusion(" in line and re.search(
                    r"copy|transpose|reshape", m.group(1)))):
            moves.append(line.strip()[:160])
    return moves


@pytest.mark.parametrize("lanes,hq,hk,dh,mb,layers,ntot", [
    (8, 16, 16, 128, 128, 24, 1025),    # pythia_chat_closed
    (64, 32, 8, 128, 64, 1, 4097),      # granite_h_chat_closed
    (128, 16, 2, 256, 128, 1, 16385),   # qwen3next_chat_closed
], ids=["pythia_1p4b", "granite_4p0_h_small_ep2", "qwen3_next_80b_a3b_ep2"])
def test_mosaic_compiles_the_kernel_at_the_cells_widths(
        one_chip, lanes, hq, hk, dh, mb, layers, ntot):
    """Each cell's arena in the order its codec makes it (2 key-value
    heads: heads-major): Mosaic takes the kernel, and the flat view the
    kernel reads is a bitcast: nothing arena-sized is copied, reshaped or
    transposed (token-major at 2 heads XLA relaid all 537 MB out a step,
    ``%reshape.2236 bf16[1,16385,2,32,256]``: PERF.md, PR 31 and PR 34)."""
    from nnstreamer_tpu.models.transformer import kv_heads_major

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    heads_major = kv_heads_major((hk, dh))
    assert heads_major == (hk == 2)
    block = (hk, 16) if heads_major else (16, hk)
    text = _compile_for_the_chip(
        jax.jit(functools.partial(
            _paged_decode, scale=0.1, chunk=8, interpret=False,
            heads_major=heads_major)),
        shape((lanes, hq, dh), jnp.bfloat16),
        shape((layers, ntot, 2) + block + (dh,), jnp.bfloat16),
        shape((), jnp.int32), shape((lanes, mb), jnp.int32),
        shape((lanes,), jnp.int32))
    assert "tpu_custom_call" in text and "nns_paged_decode" in text
    # the arena goes to the kernel as it lies: a bitcast, never a copy
    assert not _moves_of(text, layers * ntot * 2 * 16 * hk * dh)


@pytest.mark.parametrize("program", ["prefill_scatter", "decode_write",
                                     "prefix_extension"])
def test_no_program_moves_the_arena_at_two_key_value_heads(one_chip,
                                                           program):
    """The other programs that hold ``qwen3next_chat_closed``'s arena
    (``[1, 16385, 2, 2, 16, 256]``, 537 MB), compiled for the chip: the
    prefill's hand-over, eight steps of the decode write with the arena a
    carry, and the prefix-extension program of a dense block with the same
    entry. None copies, reshapes or transposes anything arena-sized, and
    none plans a temporary near it (token-major the hand-over copied the
    arena into ``{5,3,4,2,1,0}`` and back, 537 MB of temporaries; a
    heads-major write with the window ``[2, h, 1, dh]`` did the same
    every step)."""
    from nnstreamer_tpu.models import transformer as tr
    from nnstreamer_tpu.serving import kvpool

    def shape(dims, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    cfg = TransformerConfig(vocab=1024, d_model=512, n_heads=2, n_layers=1,
                            d_ff=1024, max_seq=2048, dtype=jnp.bfloat16)
    codec = tr._kv_codec(cfg, None)
    assert codec.heads_major
    arena = jax.eval_shape(lambda: codec.paged_init(1, 16385, 16, 2, 256))
    assert arena.shape == (1, 16385, 2, 2, 16, 256)
    arena = shape(arena.shape, arena.dtype)
    if program == "prefill_scatter":
        fn = jax.jit(functools.partial(kvpool._scatter_prefill_impl,
                                       heads_major=True),
                     donate_argnums=(0,))
        args = (arena, shape((1, 2, 1, 512, 2, 256), jnp.bfloat16),
                shape((32,)))
    elif program == "decode_write":
        def steps(pages, kv, blk, off):
            def body(carry, _):
                pages, off = carry
                pages = codec.paged_write(pages, jnp.int32(0), kv, blk, off)
                return (pages, (off + 1) % 16), pages[0, 0, 0, 0, 0, 0]
            return jax.lax.scan(body, (pages, off), None, length=8)

        fn = jax.jit(steps, donate_argnums=(0,))
        args = (arena, shape((2, 128, 1, 2, 256), jnp.bfloat16),
                shape((128, 1)), shape((128, 1)))
    else:
        params = jax.eval_shape(lambda: tr.init_params(cfg, 0))
        fn = jax.jit(tr.build_paged_chunk(cfg, 16), donate_argnums=(2,))
        args = (jax.tree.map(lambda a: shape(a.shape, a.dtype), params),
                shape((1, 64)), arena, shape((1, 128)), shape((1,)),
                shape((1,)))
    compiled = _compiled_for_the_chip(fn, *args)
    assert not _moves_of(compiled.as_text(), 16385 * 2 * 2 * 16 * 256)
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2 ** 20


def _compiled_for_the_chip(fn, *shapes, **kw):
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return fn.lower(*shapes, **kw).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()


def _compile_for_the_chip(fn, *shapes, **kw):
    return _compiled_for_the_chip(fn, *shapes, **kw).as_text()


def test_mosaic_compiles_the_latent_kernel_at_the_cells_widths(one_chip):
    """``dsv2lite_longctx_closed``: 64 lanes, 16 heads against rows of 576
    columns held at 640 (Mosaic moves whole 128-lane tiles: a slice of
    576 it refuses) of which the first 512 are the value, 256 blocks a
    table, 14 layers: one DMA a block, and the arena goes in as it lies."""
    from nnstreamer_tpu.ops.paged_attention import LATENT_CHUNK_BLOCKS

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    text = _compile_for_the_chip(
        jax.jit(functools.partial(
            _paged_decode, scale=0.1, chunk=LATENT_CHUNK_BLOCKS,
            interpret=False, v_width=512)),
        shape((64, 16, 640), jnp.bfloat16),
        shape((14, 16385, 1, 16, 640), jnp.bfloat16),
        shape((), jnp.int32), shape((64, 256), jnp.int32),
        shape((64,), jnp.int32))
    assert "tpu_custom_call" in text and "nns_mla_paged_decode" in text
    assert "bf16[64,16,512]" in text
    assert not [line for line in text.splitlines()
                if " copy(" in line
                and "bf16[14,16385,1," in line.split(" copy(")[0]]


@pytest.mark.parametrize("s", [1536, 3072])
def test_mosaic_compiles_flash_prefill_with_narrower_values(one_chip, s):
    """Queries and keys 192 wide against values of 128, 16 heads, at the
    two prefill buckets the latent cell's traffic runs, under the tiles
    the plan makes of them (768 and 1024 square)."""
    from nnstreamer_tpu.ops.flash_attention import _flash_bhsd, tile_plan

    def shape(dims):
        return jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=one_chip)

    plan = tile_plan(s, s, 192, 128, None, jnp.bfloat16)
    assert plan.block_q == plan.block_k == {1536: 768, 3072: 1024}[s]
    text = _compile_for_the_chip(
        _flash_bhsd, shape((1, 16, s, 192)), shape((1, 16, s, 192)),
        shape((1, 16, s, 128)), causal=True, block_q=plan.block_q,
        block_k=plan.block_k, interpret=False, scale=0.1147)
    assert "tpu_custom_call" in text and "nns_flash_prefill" in text
    assert f"bf16[1,16,{s},128]" in text


@pytest.mark.parametrize("dtype,hq,hk,d,s", [
    (jnp.bfloat16, 16, 2, 256, 512),     # qwen3next_chat_closed's longest
    (jnp.bfloat16, 16, 2, 256, 4096),
    (jnp.float32, 16, 2, 256, 4096),     # 512 rows: 1024 do not fit VMEM
    (jnp.float32, 8, 8, 128, 4096),      # eight heads of 128, f32
    (jnp.bfloat16, 16, 16, 64, 2048),
], ids=lambda x: str(getattr(x, "__name__", x)))
def test_mosaic_compiles_the_flash_prefill_under_the_plans_tiles(
        one_chip, dtype, hq, hk, d, s):
    """Whatever tiles ``tile_plan`` makes of a head width and a dtype,
    Mosaic takes them: its VMEM estimate errs on the safe side."""
    from nnstreamer_tpu.ops.flash_attention import _flash_bhsd, tile_plan

    def shape(heads):
        return jax.ShapeDtypeStruct((1, heads, s, d), dtype,
                                    sharding=one_chip)

    plan = tile_plan(s, s, d, d, None, dtype)
    text = _compile_for_the_chip(
        _flash_bhsd, shape(hq), shape(hk), shape(hk), causal=True,
        block_q=plan.block_q, block_k=plan.block_k, interpret=False)
    assert "tpu_custom_call" in text and "nns_flash_prefill" in text


@pytest.mark.parametrize("n_held,d,f,tile,rows", [
    (256, 2048, 512, 8, 3072),      # qwen3next_chat_closed, decode
    (256, 2048, 512, 32, 13056),    # ... its 512-token prefill
    (36, 4096, 768, 32, 1760),      # granite_h_chat_closed, decode
    (36, 4096, 768, 128, 9728),     # ... its 512-token prefill
], ids=["qwen3_next_decode", "qwen3_next_prefill", "granite_decode",
        "granite_prefill"])
def test_mosaic_compiles_the_expert_kernel_at_the_cells_widths(
        one_chip, n_held, d, f, tile, rows):
    """The routed experts' grouped matmul (``ops/grouped_matmul.py``; here
    because a process describes the chip once, and this file does) with
    the blocks its own rule gives for a v5e: whole experts, no copy of a
    weight stack on the way in."""
    from nnstreamer_tpu.ops import grouped_matmul as gm

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    from jax.experimental.compilation_cache import compilation_cache

    f_chunk, limit = gm.expert_blocks(d, f, tile, jnp.bfloat16)
    assert f_chunk == f
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = gm._expert_tiles.lower(
            shape((rows, d), jnp.bfloat16), shape((rows // tile,), jnp.int32),
            shape((), jnp.int32), shape((n_held, d, 2 * f), jnp.bfloat16),
            shape((n_held, f, d), jnp.bfloat16), tile=tile, f_chunk=f_chunk,
            vmem_limit_bytes=limit, interpret=False).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    assert "tpu_custom_call" in text and "nns_expert_tiles" in text
    assert not [line for line in text.splitlines()
                if " copy(" in line and f"bf16[{n_held}," in line]


@pytest.mark.parametrize("rule,arena", [
    ("mamba2", (9, 64, 128, 64, 128)),          # granite_h_chat_closed
    ("gated_delta", (3, 128, 32, 128, 128)),    # qwen3next_chat_closed
    ("mamba1", (9, 64, 1, 16, 5120)),           # phi4flash_reason_closed
], ids=["granite_mamba2", "qwen3_next_gated_delta", "phi4flash_mamba1"])
def test_mosaic_compiles_the_lane_state_kernel_at_the_cells_shapes(
        one_chip, rule, arena):
    """The recurrent mixers' state update (``ops/lane_state.py``; here for
    the reason above) with the head block its own rule gives for a v5e,
    every layer a step, eight steps with the arena the scan's carry as the
    decode program holds it (32 and 16 heads a grid step): Mosaic takes
    both bodies, and nothing of the
    arena's or a layer's shape is copied, selected or sliced round them
    (2.42 GB and 805 MB: a copy would be the whole gain, and the memory)."""
    from nnstreamer_tpu.ops import lane_state as ls

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    layers, lanes, heads, rows, cols = arena
    hb, limit = ls.head_block(heads, rows * cols * 4)
    # a MiB of tiles a step; a Mamba-1 lane's one tile is 320 KiB
    assert hb * rows * cols * 4 == (320 << 10 if rule == ls.MAMBA1
                                    else 1 << 20)
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
    per_head, vector = f32((lanes, heads)), f32((lanes, heads, rows))
    by_col = f32((lanes, heads, cols))
    operands = (vector, per_head, f32((heads,)), f32((lanes, cols)),
                f32((lanes, cols))) if rule == ls.MAMBA2 else (
        by_col, by_col, f32((heads, rows, cols)), f32((lanes, rows)),
        f32((lanes, rows))) if rule == ls.MAMBA1 else (
        vector, vector, by_col, per_head, per_head)
    vectors = [shape(v.shape) for v in jax.eval_shape(
        lambda ops: ls._RULES[rule].pack(ops, hb), operands)]

    def steps(state, live, vectors):
        def body(state, _):
            total = 0.0
            for layer in range(layers):
                out, state = ls._lane_state(
                    state, live, vectors, rule=rule, layer=layer, hb=hb,
                    vmem_limit_bytes=limit, interpret=False)
                total = total + out[0, 0, 0, 0]
            return state, total
        return jax.lax.scan(body, state, None, length=8)

    compiled = _compiled_for_the_chip(
        jax.jit(steps, donate_argnums=(0,)), shape(arena),
        shape((lanes,), jnp.int32), vectors)
    text = compiled.as_text()
    assert text.count("nns_lane_state") >= layers
    # the live lanes alone ride in by scalar prefetch: a float32 scalar a
    # lane and head there (32-48 KB) ran, and then no later program that
    # prefetches scalars came back from the chip (PERF.md, PR 36)
    assert f"f32[{lanes * heads}]" not in text
    sized = ["f32[" + ",".join(map(str, dims)) + "]"
             for dims in (arena, arena[1:])]
    moved = [line.strip()[:160] for line in text.splitlines()
             if " = " in line and any(
                 s in line.split(" = ", 1)[1].split("(")[0] for s in sized)
             and re.search(r"[\]})] (copy|select|dynamic-update-slice|"
                           r"dynamic-slice|fusion)\(", line)]
    assert not moved
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2 ** 20


# -- a window: the lower bound beside pos -------------------------------------

def _window_form(q, pages, layer, bt, pos, scale, window, heads_major=False):
    """Every slot of the whole table under the band mask ``pos - window <
    slot <= pos``: what a window means, with nothing skipped."""
    g = _paged_gather(pages, layer, bt, heads_major)
    slots = jnp.arange(g.shape[2])[None, None, None, :]
    at = pos[:, None, None, None]
    return _attend_cache(q, g[:, 0], g[:, 1], (slots <= at)
                         & (slots > at - window), q.shape[-1], q.dtype,
                         scale=scale)


def _behind_the_window_given_back(bt, pos, window, t):
    """The table as the engine leaves it: the entries of the blocks that
    lie wholly before ``pos - window + 1`` are the sentinel again."""
    bt = np.array(bt)
    first = np.maximum(np.asarray(pos) - window + 1, 0) // t
    for lane in range(bt.shape[0]):
        bt[lane, :first[lane]] = bt.max()     # the sentinel
    return jnp.asarray(bt)


@pytest.mark.parametrize("chunk", [2, 8], ids=["chunk2", "chunk8"])
@pytest.mark.parametrize("window", [1, 5, T, 2 * T + 3, 4 * T, 1000],
                         ids=lambda w: f"w{w}")
@pytest.mark.parametrize("hq,hk,dtype,tol,heads_major", [
    (16, 8, jnp.float32, 1e-5, False), (48, 8, jnp.bfloat16, 2e-2, False),
    (16, 2, jnp.float32, 1e-5, True)],
    ids=["16over8_f32", "48over8_bf16", "16over2_heads_major"])
def test_window_kernel_equals_the_band_mask_over_the_whole_table(
        hq, hk, dtype, tol, heads_major, window, chunk):
    """Ragged positions (the edges of a block, mid-table, an empty lane), a
    window that starts mid-block, at a block's edge, and one wider than any
    context; the blocks behind the window GIVEN BACK (their entries the
    sentinel): the kernel and the gather form read none of them."""
    pages, bt, pos, rng = _pool(hk, dtype, seed=hq + window,
                                heads_major=heads_major)
    q = jnp.asarray(rng.standard_normal((len(HELD), 1, hq, DH)), dtype)
    given_back = _behind_the_window_given_back(bt, pos, window, T)
    for layer in range(LAYERS):
        want = _window_form(q, pages, layer, bt, pos, 0.3, window,
                            heads_major)
        for form in ("pallas", "reference"):
            got = paged_attention(q, pages, layer, given_back, pos,
                                  scale=0.3, force=form, chunk_blocks=chunk,
                                  heads_major=heads_major, window=window)
            assert got.shape == want.shape and got.dtype == want.dtype
            np.testing.assert_allclose(np.asarray(got, np.float32),
                                       np.asarray(want, np.float32),
                                       rtol=tol, atol=tol)
            assert not np.asarray(got, np.float32)[-1].any()  # empty lane
    if window >= MB * T:   # wider than any context: the plain kernel's
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(paged_attention(
                q, pages, LAYERS - 1, bt, pos, scale=0.3, force="reference",
                heads_major=heads_major), np.float32), rtol=tol, atol=tol)


def test_a_window_is_refused_where_it_is_not_built():
    pages, bt, pos, rng = _pool(8, jnp.float32, seed=1)
    q = jnp.asarray(rng.standard_normal((len(HELD), 1, 16, DH)), jnp.float32)
    with pytest.raises(ValueError, match="positive"):
        paged_attention(q, pages, 0, bt, pos, window=0)
    latent, lbt, lpos, _ = _latent_pool(jnp.float32, seed=2)
    lq = jnp.zeros((len(HELD), 1, 8, latent.shape[-1]), jnp.float32)
    with pytest.raises(ValueError, match="window over a latent arena"):
        paged_attention(lq, latent, 0, lbt, lpos, v_width=128, window=4,
                        force="pallas")
    assert paged_attention_form(q, pages, bt, window=4) == "gather"  # CPU


@pytest.mark.parametrize("name,layers,blocks,window", [
    ("nns_window_paged_decode", 4, 258, 4096),
    ("nns_paged_decode", 1, 896, None)], ids=["window", "full"])
def test_mosaic_compiles_both_trinity_kernels_with_the_112_kb_table(
        one_chip, name, layers, blocks, window):
    """``trinity_longctx_closed``: 32 lanes, 48 query over 8 key-value
    heads of 128, token-major blocks of 16; both kernels take a table of
    32 x 896 int32 (112 KB of scalar prefetch: the window table keeps the
    full table's indexing); the window arena holds 258 blocks a lane, the
    full arena 896. Each arena goes in as it lies."""
    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    ntot = 32 * blocks + 1
    text = _compile_for_the_chip(
        jax.jit(functools.partial(_paged_decode, scale=0.088, chunk=8,
                                  interpret=False, window=window)),
        shape((32, 48, 128), jnp.bfloat16),
        shape((layers, ntot, 2, 16, 8, 128), jnp.bfloat16),
        shape((), jnp.int32), shape((32, 896), jnp.int32),
        shape((32,), jnp.int32))
    assert "tpu_custom_call" in text and name in text
    assert "s32[32,896]" in text
    assert not _moves_of(text, layers * ntot * 2 * 16 * 8 * 128)


def test_mosaic_compiles_the_trinity_decode_program(one_chip):
    """The whole K-step decode program of the cell's configuration for the
    chip, both tables and both arenas its arguments: 4 window layers and 1
    full layer go through their kernels, and nothing arena-sized is copied
    or transposed on the way (the arenas are donated carries)."""
    from benchmark import run as bench_run
    from benchmark.drivers import lm_afmoe

    def shape(dims, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    cfg = lm_afmoe.afmoe_config(
        bench_run.load_cell("trinity_longctx_closed")["config"])
    params = jax.tree.map(
        lambda a: shape(a.shape, a.dtype),
        jax.eval_shape(lambda: cfg.family.init_params(cfg, 0)))

    def attend(q, pages, layer, bt, pos_c, scale=None, heads_major=False,
               window=None):   # the chip's choice, made here: no TPU
        return _paged_decode(q[:, 0], pages, layer, bt, pos_c,
                             scale=float(scale), chunk=8, interpret=False,
                             heads_major=heads_major, window=window)[:, None]

    step = cfg.family.build_paged_decode_step(cfg, 16, 14336,
                                              paged_attention_fn=attend)

    def dispatch(params, token, arenas, bt, pos):
        def body(carry, _):
            token, arenas, pos = carry
            logits, arenas, _ = step(params, token, arenas, bt, pos)
            token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (token, arenas, pos + 1), token
        return jax.lax.scan(body, (token, arenas, pos), None, length=8)

    arenas = {"kv": shape((1, 32 * 896 + 1, 2, 16, 8, 128), jnp.bfloat16),
              "win": shape((4, 32 * 258 + 1, 2, 16, 8, 128), jnp.bfloat16)}
    compiled = _compiled_for_the_chip(
        jax.jit(dispatch, donate_argnums=(2,)), params, shape((32,)), arenas,
        {"kv": shape((32, 896)), "win": shape((32, 896))}, shape((32,)))
    text = compiled.as_text()
    assert text.count("nns_window_paged_decode") >= 4
    assert "nns_paged_decode" in text.replace("nns_window_paged_decode", "")
    assert not _moves_of(text, 32 * 258 * 2 * 16 * 8 * 128)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30


def test_mosaic_compiles_the_phi4flash_decode_program(one_chip, monkeypatch):
    """The whole K-step decode program of ``phi4flash_reason_closed`` for
    the chip, all 32 layers, its three kinds of lane memory its arguments:
    8 window layers through ``nns_window_paged_decode``, the ONE full layer
    and the 7 cross layers that own no cache through ``nns_paged_decode``
    over the same arena index (40 query rows ``[q1 | 0]`` / ``[0 | q2]``
    over 10 key-value pairs of 128, heads-major: 10 heads are not whole
    tiles, and token-major XLA relaid both arenas out every step, 3.3 GB
    of temporaries that do not fit: PERF.md, PR 39), 9 Mamba-1 layers
    through ``nns_lane_state``; both tables are 64 x 400 int32 (102 KB of
    scalar prefetch); nothing arena-sized is copied or transposed, and the
    program plans next to no temporaries beside 10.7 GB of arguments."""
    from benchmark import run as bench_run
    from benchmark.drivers import lm_sambay
    from nnstreamer_tpu.ops import lane_state as ls

    def shape(dims, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    cfg = lm_sambay.sambay_config(
        bench_run.load_cell("phi4flash_reason_closed")["config"])
    params = jax.tree.map(
        lambda a: shape(a.shape, a.dtype),
        jax.eval_shape(lambda: cfg.family.init_params(cfg, 0)))

    def attend(q, pages, layer, bt, pos_c, scale=None, heads_major=False,
               window=None, scope=None):   # the chip's choice, made here
        assert heads_major
        return _paged_decode(q[:, 0], pages, layer, bt, pos_c,
                             scale=float(scale), chunk=8, interpret=False,
                             heads_major=True, window=window)[:, None]

    def update(rule, slot, live, operands, force=None):   # likewise
        arena, layer = slot
        _, lanes, heads, rows, cols = arena.shape
        hb, limit = ls.head_block(heads, rows * cols * 4)
        out, new = ls._lane_state(
            arena, live, ls._RULES[rule].pack(operands, hb), rule=rule,
            layer=int(layer), hb=hb, vmem_limit_bytes=limit,
            interpret=False)
        return out.reshape(lanes, heads, -1), ls.LaneSlot(new, layer)

    monkeypatch.setattr(ls, "update", update)
    step = cfg.family.build_paged_decode_step(cfg, 16, 6400,
                                              paged_attention_fn=attend)

    def dispatch(params, token, arenas, bt, pos):
        def body(carry, _):
            token, arenas, pos = carry
            logits, arenas, _ = step(params, token, arenas, bt, pos)
            token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (token, arenas, pos + 1), token
        return jax.lax.scan(body, (token, arenas, pos), None, length=8)

    arenas = {"kv": shape((1, 64 * 400 + 1, 2, 10, 16, 128), jnp.bfloat16),
              "win": shape((8, 64 * 34 + 1, 2, 10, 16, 128), jnp.bfloat16),
              "state": {"ssm": shape((9, 64, 1, 16, 5120), jnp.float32),
                        "conv": shape((9, 64, 3, 5120), jnp.bfloat16)}}
    compiled = _compiled_for_the_chip(
        jax.jit(dispatch, donate_argnums=(2,)), params, shape((64,)), arenas,
        {"kv": shape((64, 400)), "win": shape((64, 400))}, shape((64,)))
    text = compiled.as_text()
    assert text.count("nns_window_paged_decode") >= 8
    assert text.replace("nns_window_paged_decode", "").count(
        "nns_paged_decode") >= 8
    assert text.count("nns_lane_state") >= 9
    assert "s32[64,400]" in text
    assert not _moves_of(text, 64 * 34 * 2 * 16 * 10 * 128)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 29


@pytest.mark.parametrize("window", [4096, None], ids=["band", "full"])
@pytest.mark.parametrize("s", [6144, 12288])
def test_mosaic_compiles_the_band_flash_prefill_at_the_cells_buckets(
        one_chip, s, window):
    """48 query heads over 8 key-value heads of 128, window 4096 and none,
    at the two prefill buckets the cell's traffic runs, under the plan's
    1024 x 1024 tiles: the k axis of the band's grid is the 6 tiles a
    band can touch, not the longer bucket's 12."""
    from nnstreamer_tpu.ops.flash_attention import _flash_bhsd, tile_plan

    def shape(heads):
        return jax.ShapeDtypeStruct((1, heads, s, 128), jnp.bfloat16,
                                    sharding=one_chip)

    plan = tile_plan(s, s, 128, 128, window, jnp.bfloat16)
    assert plan[:3] == (1024, 1024, s // 1024)
    assert plan.k_steps == (6 if window else s // 1024)
    text = _compile_for_the_chip(
        _flash_bhsd, shape(48), shape(8), shape(8), causal=True,
        block_q=plan.block_q, block_k=plan.block_k, interpret=False,
        scale=0.088, window=window)
    name = "nns_band_flash_prefill" if window else "nns_flash_prefill"
    assert "tpu_custom_call" in text and name in text
    assert f"bf16[1,48,{s},128]" in text
