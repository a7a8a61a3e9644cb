"""fast_init — shape-based parameter materialization (models/_init.py).

The zoo factories must initialize in ~ms (not run the un-jitted forward:
flax ``init`` took ~34 s for MobileNetV2 on a 1-core host) while keeping
the exact variable-tree structure flax would produce and staying
deterministic across processes (crc32 path keying, not salted hash()).
"""

import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from nnstreamer_tpu.models._init import fast_init


def _tiny_model():
    import flax.linen as nn

    class M(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = nn.Conv(8, (3, 3), use_bias=True)(x)
            x = nn.BatchNorm(use_running_average=True)(x)
            x = nn.relu(x)
            return nn.Dense(4)(x.mean(axis=(1, 2)))

    return M()


def test_same_tree_as_flax_init():
    m = _tiny_model()
    rng = jax.random.PRNGKey(0)
    x = jnp.zeros((1, 8, 8, 3))
    ref = m.init(rng, x)
    fast = fast_init(m.init, rng, x)
    ref_paths = jax.tree_util.tree_flatten_with_path(ref)[0]
    fast_paths = jax.tree_util.tree_flatten_with_path(fast)[0]
    assert len(ref_paths) == len(fast_paths)
    for (rp, rv), (fp, fv) in zip(ref_paths, fast_paths):
        assert rp == fp
        assert rv.shape == fv.shape
        assert rv.dtype == fv.dtype


def test_statistics_and_specials():
    m = _tiny_model()
    v = fast_init(m.init, jax.random.PRNGKey(0), jnp.zeros((1, 8, 8, 3)))
    bs = v["batch_stats"]["BatchNorm_0"]
    assert np.all(np.asarray(bs["mean"]) == 0)
    assert np.all(np.asarray(bs["var"]) == 1)
    p = v["params"]
    assert np.all(np.asarray(p["BatchNorm_0"]["scale"]) == 1)
    assert np.all(np.asarray(p["Conv_0"]["bias"]) == 0)
    k = np.asarray(p["Conv_0"]["kernel"])
    assert k.std() > 0  # actually random
    fan_in = k.shape[0] * k.shape[1] * k.shape[2]
    assert abs(k.std() - 1 / np.sqrt(fan_in)) < 0.5 / np.sqrt(fan_in)


def test_deterministic_in_process():
    m = _tiny_model()
    rng = jax.random.PRNGKey(0)
    x = jnp.zeros((1, 8, 8, 3))
    a = fast_init(m.init, rng, x, seed=7)
    b = fast_init(m.init, rng, x, seed=7)
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        assert np.array_equal(np.asarray(la), np.asarray(lb))
    c = fast_init(m.init, rng, x, seed=8)
    assert any(
        not np.array_equal(np.asarray(la), np.asarray(lc))
        for la, lc in zip(jax.tree_util.tree_leaves(a),
                          jax.tree_util.tree_leaves(c))
    )


def test_deterministic_across_processes():
    # hash() is salted per-process; crc32 keying must not be. Fingerprint a
    # kernel in a fresh interpreter (different PYTHONHASHSEED) and compare.
    prog = (
        "import os; os.environ['JAX_PLATFORMS']='cpu';"
        "import jax, jax.numpy as jnp, numpy as np;"
        "import flax.linen as nn;"
        "from nnstreamer_tpu.models._init import fast_init\n"
        "class M(nn.Module):\n"
        "    @nn.compact\n"
        "    def __call__(self, x):\n"
        "        return nn.Dense(4)(x)\n"
        "v = fast_init(M().init, jax.random.PRNGKey(0), jnp.zeros((1, 3)))\n"
        "print(float(np.asarray(v['params']['Dense_0']['kernel']).sum()))"
    )
    import os

    env = dict(os.environ, PYTHONHASHSEED="12345")
    out = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        env=env, check=True, cwd=os.path.dirname(os.path.dirname(__file__)),
    )
    remote = float(out.stdout.strip().splitlines()[-1])

    import flax.linen as nn

    class M(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(4)(x)

    v = fast_init(M().init, jax.random.PRNGKey(0), jnp.zeros((1, 3)))
    local = float(np.asarray(v["params"]["Dense_0"]["kernel"]).sum())
    assert abs(local - remote) < 1e-6
