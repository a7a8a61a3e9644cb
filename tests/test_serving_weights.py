"""The engine holds its weights at the width its programs read them
(``models/family.py serving_params``): what a family's programs read as
``leaf.astype(cfg.dtype)`` is rounded to ``cfg.dtype`` once, at
construction, and everything else is held as given.

The bar: the same work, not less of it. Rounding a float32 weight once and
reading the copy at every step is the arithmetic of a program that is
handed the float32 tree and rounds at every call, so served tokens AND
log-probabilities are bit-equal to the builders run directly on the
float32 tree."""

import gc
import weakref

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from nnstreamer_tpu.models import hybrid  # noqa: E402
from nnstreamer_tpu.models.family import serving_params  # noqa: E402
from nnstreamer_tpu.models.transformer import (  # noqa: E402
    TransformerConfig,
    build_paged_decode_step,
    build_prefill,
    init_params,
    make_sampler,
)
from nnstreamer_tpu.serving import ContinuousBatchingEngine  # noqa: E402
from nnstreamer_tpu.tensors import memory  # noqa: E402

T, K, SEED = 8, 4, 11
DENSE = TransformerConfig(vocab=97, d_model=64, n_heads=4, n_layers=2,
                          d_ff=160, max_seq=64, dtype=jnp.bfloat16)
MOE = TransformerConfig(vocab=97, d_model=64, n_heads=4, n_layers=2,
                        d_ff=160, max_seq=64, dtype=jnp.bfloat16,
                        num_experts=3)
CONFIGS = {"dense": DENSE, "moe": MOE}
NARROWED = ("qkv", "proj", "w_in", "w_out")
PROMPT = [5, 11, 23, 42, 7, 3, 9, 61, 17, 2, 88]


def wide_tree(cfg, seed=3):
    return init_params(cfg, seed)            # float32, as the model stores


def nbytes(tree):
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))


def engine(cfg, params, **kw):
    kw.setdefault("max_streams", 1)
    kw.setdefault("steps_per_dispatch", K)
    kw.setdefault("block_tokens", T)
    kw.setdefault("attention", "reference")
    kw.setdefault("seed", SEED)
    return ContinuousBatchingEngine(cfg, params, **kw)


def served(eng, n_new, prompt=PROMPT):
    eng.start()
    try:
        stream = eng.submit(prompt, max_new_tokens=n_new)
        stream.result(timeout=240)
    finally:
        eng.stop()
    return (np.asarray(stream.tokens, np.int32),
            np.asarray(stream.logprobs, np.float32))


def direct(cfg, params, n_new, temperature, prompt=PROMPT):
    """One stream through ``build_prefill`` + ``build_paged_decode_step``
    called directly on ``params``, laid out as the engine lays a lone
    first stream out: the prompt right-padded to its bucket, the stream's
    key ``(seed, 0)``, the prefill's cache in blocks 0.. of an arena, K
    steps a program."""
    S, L = cfg.max_seq, cfg.n_layers
    MB, n = S // T, len(prompt)
    sample = make_sampler(cfg.vocab, temperature, with_logprobs=True)
    prefill = jax.jit(build_prefill(cfg, S))
    step = build_paged_decode_step(cfg, T, S)
    padded = np.zeros((1, 16), np.int32)
    padded[0, :n] = prompt
    logits, cache1 = prefill(params, jnp.asarray(padded),
                             lengths=jnp.asarray([n], jnp.int32))
    tok, keys, lp = jax.jit(sample)(
        logits, jnp.asarray([[SEED, 0]], jnp.uint32))
    # 4 heads: the arena's blocks are heads-major, [.., 2, h, T, dh]
    blocks = cache1[:, :, 0].reshape(
        L, 2, MB, T, cfg.n_heads, cfg.head_dim).transpose(0, 2, 1, 4, 3, 5)
    arena = jnp.zeros((L, MB + 1) + blocks.shape[2:], cfg.dtype
                      ).at[:, :MB].set(blocks)
    bt = jnp.arange(MB, dtype=jnp.int32)[None]

    @jax.jit
    def dispatch(params, token, arena, pos, keys):
        def body(carry, _):
            token, arena, pos, keys = carry
            logits, arena = step(params, token, arena, bt, pos)
            nxt, keys, lp = sample(logits, keys)
            return (nxt, arena, pos + 1, keys), (nxt, lp)

        (token, arena, pos, keys), (toks, lps) = jax.lax.scan(
            body, (token, arena, pos, keys), None, length=K)
        return toks[:, 0], lps[:, 0], token, arena, pos, keys

    toks, lps = [np.asarray(tok)], [np.asarray(lp)]
    pos = jnp.asarray([n], jnp.int32)
    while sum(t.size for t in toks) < n_new:
        t, l, tok, arena, pos, keys = dispatch(params, tok, arena, pos,
                                               keys)
        toks.append(np.asarray(t))
        lps.append(np.asarray(l))
    return (np.concatenate(toks)[:n_new].astype(np.int32),
            np.concatenate(lps)[:n_new].astype(np.float32))


# -- (a) the same arithmetic ------------------------------------------------

@pytest.mark.parametrize("temperature", [0.0, 0.8],
                         ids=["greedy", "sampled"])
@pytest.mark.parametrize("name", ["dense", "moe"])
def test_served_bits_equal_the_builders_on_the_float32_tree(name,
                                                            temperature):
    cfg = CONFIGS[name]
    wide = wide_tree(cfg)
    eng = engine(cfg, wide, temperature=temperature)
    assert eng.weights["weight_leaves_narrowed"] == 4
    toks, lps = served(eng, 13)
    ref_toks, ref_lps = direct(cfg, wide, 13, temperature)
    assert toks.tolist() == ref_toks.tolist()
    assert lps.tobytes() == ref_lps.tobytes(), (lps, ref_lps)


@pytest.mark.parametrize("name", ["dense", "moe"])
def test_builders_on_the_held_tree_equal_the_float32_tree_bit_for_bit(name):
    """One prefill and one paged decode step, wide tree against held
    tree, every logit and every key and value."""
    cfg = CONFIGS[name]
    wide = wide_tree(cfg)
    held, _ = serving_params(cfg, wide)
    prefill = jax.jit(build_prefill(cfg, cfg.max_seq))
    tokens = jnp.asarray([PROMPT + [0] * 5], jnp.int32)
    lengths = jnp.asarray([len(PROMPT)], jnp.int32)
    step = jax.jit(build_paged_decode_step(cfg, T, cfg.max_seq))
    arena = jax.random.normal(
        jax.random.PRNGKey(1),
        (cfg.n_layers, 9, 2, cfg.n_heads, T, cfg.head_dim)).astype(cfg.dtype)
    bt = jnp.arange(8, dtype=jnp.int32)[None]
    args = (jnp.asarray([7], jnp.int32), arena, bt,
            jnp.asarray([19], jnp.int32))
    for a, b in zip(jax.tree.leaves((prefill(wide, tokens, lengths),
                                     step(wide, *args))),
                    jax.tree.leaves((prefill(held, tokens, lengths),
                                     step(held, *args)))):
        assert a.dtype == b.dtype
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


# -- (b) the held tree -------------------------------------------------------

@pytest.mark.parametrize("name", ["dense", "moe"])
def test_held_tree_is_narrow_where_programs_read_narrow(name):
    cfg = CONFIGS[name]
    wide = wide_tree(cfg)
    given = nbytes(wide)
    as_given = {k: v for k, v in wide.items() if k not in NARROWED}
    gone = [weakref.ref(wide[k]) for k in NARROWED]
    eng = engine(cfg, wide)
    del wide
    gc.collect()
    for k in NARROWED:
        assert eng.params[k].dtype == cfg.dtype, k
    for k, v in as_given.items():      # scales, router, the head's table
        assert eng.params[k] is v and v.dtype == jnp.float32, k
    assert [r() for r in gone] == [None] * 4, \
        "the engine keeps the float32 arrays it was given alive"
    assert eng.weights == {
        "weight_bytes_given": given,
        "weight_bytes_held": nbytes(eng.params),
        "weight_leaves_narrowed": 4}
    assert eng.weights["weight_bytes_held"] < given


def test_weights_record_is_exported_as_gauges():
    from nnstreamer_tpu.obs import get_registry

    eng = engine(DENSE, wide_tree(DENSE))
    text = get_registry().render_prometheus()
    for key, value in eng.weights.items():
        line = f'nns_serving_{key}{{engine="{eng.obs_name}"}} {value}'
        assert line in text, line


# -- (c), (d) what is passed through ----------------------------------------

def test_a_tree_already_in_dtype_is_held_as_the_same_arrays():
    stored = {k: v.astype(DENSE.dtype) if k in NARROWED else v
              for k, v in wide_tree(DENSE).items()}
    eng = engine(DENSE, stored)
    assert all(eng.params[k] is v for k, v in stored.items())
    assert eng.weights == {"weight_bytes_given": nbytes(stored),
                           "weight_bytes_held": nbytes(stored),
                           "weight_leaves_narrowed": 0}


HYBRID = dict(
    vocab=53, d_model=32, layer_types=("mamba", "attention"), n_heads=2,
    n_kv_heads=1, head_dim=16, ssm_heads=4, ssm_head_dim=8, ssm_state=16,
    ssm_chunk=16, num_experts=4, experts_per_token=2, expert_width=16,
    shared_width=16, experts_held=(0, 2), max_seq=64)


def test_hybrid_family_stores_what_it_reads_and_passes_through():
    cfg = hybrid.HybridConfig(**HYBRID)
    assert cfg.dtype == cfg.param_dtype == jnp.bfloat16
    params = hybrid.init_params(cfg, seed=1)
    eng = engine(cfg, params, max_streams=2)
    given, held = jax.tree.leaves(params), jax.tree.leaves(eng.params)
    assert len(given) == len(held)
    assert all(a is b for a, b in zip(given, held))
    assert eng.weights == {"weight_bytes_given": nbytes(params),
                           "weight_bytes_held": nbytes(params),
                           "weight_leaves_narrowed": 0}


def test_hybrid_family_narrows_its_matrices_and_not_the_lookup_table():
    cfg = hybrid.HybridConfig(**HYBRID, param_dtype=jnp.float32)
    held, record = serving_params(cfg, hybrid.init_params(cfg, seed=1))
    names = set(cfg.family.read_in_dtype)
    for layer in held["layers"]:
        for k, v in layer.items():
            assert v.dtype == (jnp.bfloat16 if k in names else jnp.float32)
    assert held["embed"].dtype == jnp.float32     # ``_embed`` widens rows
    assert record["weight_leaves_narrowed"] == sum(
        k in names for layer in held["layers"] for k in layer)


@pytest.mark.parametrize("name", ["dense", "moe"])
def test_a_float32_configuration_narrows_nothing(name):
    import dataclasses

    cfg = dataclasses.replace(CONFIGS[name], dtype=jnp.float32)
    wide = wide_tree(cfg)
    eng = engine(cfg, wide)
    assert all(eng.params[k] is v for k, v in wide.items())
    assert eng.weights["weight_leaves_narrowed"] == 0
    assert eng.weights["weight_bytes_held"] == nbytes(wide)


# -- (e) no program casts a weight ------------------------------------------

def weight_casts(jaxpr, params):
    """``convert_element_type`` equations, at any depth, whose operand has
    the shape of a parameter leaf or of one layer's slice of it."""
    shapes = {s for a in jax.tree.leaves(params)
              for s in (a.shape, a.shape[1:]) if s}
    found = []

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "convert_element_type" and \
                    eqn.invars[0].aval.shape in shapes:
                found.append(eqn.invars[0].aval.shape)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    return found


@pytest.mark.parametrize("program", ["dispatch", "prefill"])
def test_programs_on_the_held_tree_cast_no_weight(program):
    wide = wide_tree(DENSE)
    eng = engine(DENSE, wide, max_streams=3)
    if program == "dispatch":
        fn = eng._build_dispatch(eng.K)
        args = (jnp.zeros(3, jnp.int32), eng._pool.arena,
                jnp.asarray(eng._bt), jnp.zeros(3, jnp.int32),
                jnp.zeros((3, 2), jnp.uint32))
    else:
        fn = eng._prefill_fn
        args = (jnp.zeros((1, 32), jnp.int32), jnp.asarray([20], jnp.int32))
    assert weight_casts(jax.make_jaxpr(fn)(eng.params, *args),
                        eng.params) == []
    # the same walk can tell: handed the float32 tree, the program rounds
    # each layer's four matrices where it multiplies them
    cast = weight_casts(jax.make_jaxpr(fn)(wide, *args), wide)
    assert sorted(cast) == sorted(wide[k].shape[1:] for k in NARROWED)


# -- (f) mesh= ---------------------------------------------------------------

def test_mesh_places_and_accounts_the_narrow_bytes():
    from nnstreamer_tpu.parallel.mesh import make_mesh

    memory.deactivate()
    acct = memory.activate(1 << 30)
    try:
        wide = wide_tree(DENSE)
        eng = engine(DENSE, wide, max_streams=2,
                     mesh=make_mesh([("dp", 2), ("tp", 2)]))
        for k in NARROWED:
            assert eng.params[k].dtype == DENSE.dtype, k
            assert len(eng.params[k].sharding.device_set) == 4
        per_device = sum(s.data.nbytes for a in jax.tree.leaves(eng.params)
                         for s in a.addressable_shards)
        assert acct._used.get("weights", 0) == per_device
        # replicated over dp, split over tp where the rule says so: under
        # what four copies of the float32 tree would be
        assert per_device < 4 * eng.weights["weight_bytes_held"] \
            < 4 * nbytes(wide)
    finally:
        memory.deactivate()


def test_one_device_engine_accounts_what_it_holds_while_it_lives():
    memory.deactivate()
    acct = memory.activate(1 << 30)
    try:
        wide = wide_tree(DENSE)
        eng = engine(DENSE, wide)
        assert acct._used.get("weights", 0) == \
            eng.weights["weight_bytes_held"]
        del eng
        gc.collect()
        assert acct._used.get("weights", 0) == 0
    finally:
        memory.deactivate()


# -- (g) speculation ----------------------------------------------------------

def test_draft_sliced_from_the_held_tree_accepts_the_same():
    """The draft is the held tree's first layers; its builders cast as the
    target's do, so it proposes, and the target accepts, what a draft
    sliced from the float32 tree would."""
    cfg = TransformerConfig(vocab=97, d_model=64, n_heads=4, n_layers=4,
                            d_ff=160, max_seq=64, dtype=jnp.bfloat16)
    wide = wide_tree(cfg)
    prompts = [PROMPT, [4, 8, 15], [16, 23, 42, 2, 2]]

    def run(from_wide):
        eng = engine(cfg, wide, max_streams=2, attention="auto",
                     temperature=0.0)
        if from_wide:   # what an engine that kept the given tree ran
            eng.params = wide
        eng.set_speculate(2, 2)
        assert eng._spec["dparams"]["qkv"].dtype == (
            jnp.float32 if from_wide else cfg.dtype)
        eng.start()
        try:
            out = [eng.generate(p, max_new_tokens=12, timeout=240)
                   for p in prompts]
        finally:
            eng.stop()
        return out, eng.stats["spec_drafted"], eng.stats["spec_accepted"]

    held, wide_run = run(False), run(True)
    assert held == wide_run
    assert held[1] > 0 and held[2] > 0
