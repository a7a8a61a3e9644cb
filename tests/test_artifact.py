"""Compiled-model artifact loading on the TPU backend.

The reference's headline capability is loading an opaque model *file* and
running it on the accelerator (tensor_filter_tensorflow_lite.cc:154-238 —
TFLiteInterpreter loads any .tflite). These tests prove the TPU-native
equivalent end to end: artifacts are produced in a *separate process*
(truly external), loaded by extension via framework=auto, self-describe
their caps, and run through SingleShot and full gst-launch pipelines.
Raw StableHLO modules — what torch_xla / TF toolchains emit — load too.
"""

import subprocess
import sys

import numpy as np
import pytest

from nnstreamer_tpu import parse_launch
from nnstreamer_tpu.filters.artifact import (
    artifact_tensors_info,
    export_model,
    load_artifact,
    save_artifact,
)
from nnstreamer_tpu.single import SingleShot
from nnstreamer_tpu.tensors.types import TensorsInfo

# Exporter script run out-of-process: a linear model with baked weights.
# JAX_PLATFORMS=cpu keeps the child off the chip (one process per chip).
_EXPORT_SCRIPT = """
import os
os.environ["JAX_PLATFORMS"] = "cpu"
import sys
import jax, jax.numpy as jnp, numpy as np
import jax.export

w = np.arange(12, dtype=np.float32).reshape(4, 3) / 10.0
b = np.array([1.0, 2.0, 3.0], dtype=np.float32)

def model(x):
    return jnp.dot(x, w) + b

exp = jax.export.export(jax.jit(model), platforms=["cpu", "tpu"])(
    jax.ShapeDtypeStruct((2, 4), jnp.float32))
with open(sys.argv[1], "wb") as f:
    f.write(bytes(exp.serialize()))
"""


def _golden(x):
    w = np.arange(12, dtype=np.float32).reshape(4, 3) / 10.0
    b = np.array([1.0, 2.0, 3.0], dtype=np.float32)
    return x @ w + b


@pytest.fixture(scope="module")
def external_artifact(tmp_path_factory):
    """An artifact produced by a separate python process."""
    path = tmp_path_factory.mktemp("artifact") / "linear.jaxexp"
    subprocess.run([sys.executable, "-c", _EXPORT_SCRIPT, str(path)],
                   check=True, capture_output=True, timeout=300)
    return str(path)


class TestExternalArtifact:
    def test_self_describing_info(self, external_artifact):
        exp = load_artifact(external_artifact)
        in_info, out_info = artifact_tensors_info(exp)
        assert in_info[0].shape == (2, 4)
        assert out_info[0].shape == (2, 3)
        assert out_info[0].type.np_dtype == np.float32

    def test_singleshot_auto_framework(self, external_artifact):
        x = np.random.default_rng(0).normal(size=(2, 4)).astype(np.float32)
        with SingleShot(model=external_artifact) as s:  # framework=auto
            assert s.get_input_info()[0].shape == (2, 4)
            (out,) = s.invoke([x])
        np.testing.assert_allclose(np.asarray(out), _golden(x),
                                   rtol=1e-5, atol=1e-5)

    def test_gst_launch_pipeline(self, external_artifact):
        """The reference's one-liner story: opaque file in a launch string,
        no input/output properties — caps come from the artifact."""
        pipe = parse_launch(
            f"appsrc name=in ! tensor_filter model={external_artifact} ! "
            "tensor_sink name=out to-host=true"
        )
        outs = []
        pipe.get("out").connect(lambda b: outs.append(b))
        x = np.full((2, 4), 0.5, dtype=np.float32)
        pipe.start()
        pipe.get("in").push([x])
        pipe.get("in").end_of_stream()
        assert pipe.wait(timeout=120).kind == "eos"
        pipe.stop()
        assert len(outs) == 1
        np.testing.assert_allclose(np.asarray(outs[0].tensors[0]),
                                   _golden(x), rtol=1e-5, atol=1e-5)


class TestSaveLoadRoundTrip:
    def test_params_baked_as_constants(self, tmp_path):
        import jax.numpy as jnp

        params = {"w": np.full((3, 3), 2.0, np.float32)}

        def fn(p, x):
            return x @ p["w"]

        info = TensorsInfo.from_str("3:5", "float32")
        path = tmp_path / "m.jaxexp"
        save_artifact(str(path), fn, params, in_info=info,
                      platforms=("cpu",))
        exp = load_artifact(str(path))
        x = np.ones((5, 3), np.float32)
        out = np.asarray(exp.call(x))
        np.testing.assert_allclose(out, x @ params["w"])

    def test_multi_output(self, tmp_path):
        import jax.numpy as jnp

        def fn(x):
            return jnp.tanh(x), x.sum(axis=1)

        info = TensorsInfo.from_str("4:2", "float32")
        path = tmp_path / "multi.stablehlo"
        save_artifact(str(path), fn, None, in_info=info, platforms=("cpu",))
        with SingleShot(framework="jax", model=str(path)) as s:
            out_info = s.get_output_info()
            assert len(out_info) == 2
            outs = s.invoke([np.ones((2, 4), np.float32)])
        np.testing.assert_allclose(np.asarray(outs[0]),
                                   np.tanh(np.ones((2, 4))), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(outs[1]), [4.0, 4.0])


class TestRawStableHLO:
    """Raw MLIR modules — the torch_xla / TF export interchange format."""

    def _mlir_text(self):
        import jax
        import jax.export
        import jax.numpy as jnp

        exp = jax.export.export(
            jax.jit(lambda x: jnp.maximum(x, 0.0) * 3.0),
            platforms=["cpu"],
        )(jax.ShapeDtypeStruct((2, 5), jnp.float32))
        return exp.mlir_module()

    def test_mlir_text_module(self, tmp_path):
        path = tmp_path / "relu3.mlir"
        path.write_text(self._mlir_text())
        with SingleShot(model=str(path)) as s:
            in_info = s.get_input_info()
            assert in_info[0].shape == (2, 5)
            x = np.linspace(-1, 1, 10, dtype=np.float32).reshape(2, 5)
            (out,) = s.invoke([x])
        np.testing.assert_allclose(np.asarray(out), np.maximum(x, 0) * 3.0,
                                   rtol=1e-6)

    def test_portable_artifact_bytes(self, tmp_path):
        import jaxlib.mlir.dialects.stablehlo as shlo

        data = shlo.serialize_portable_artifact_str(
            self._mlir_text(), shlo.get_minimum_version())
        path = tmp_path / "relu3.mlirbc"
        path.write_bytes(bytes(data))
        with SingleShot(model=str(path)) as s:
            x = np.full((2, 5), -2.0, np.float32)
            (out,) = s.invoke([x])
        np.testing.assert_allclose(np.asarray(out), 0.0)

    def test_ingested_artifact_has_no_vjp(self, tmp_path):
        path = tmp_path / "m.mlir"
        path.write_text(self._mlir_text())
        exp = load_artifact(str(path))
        assert not exp.has_vjp()


class TestExportTool:
    def test_export_model_from_py(self, tmp_path):
        src = tmp_path / "double.py"
        src.write_text(
            "import jax.numpy as jnp\n"
            "from nnstreamer_tpu.tensors.types import TensorsInfo\n"
            "IN_INFO = TensorsInfo.from_str('4:2', 'float32')\n"
            "def get_model():\n"
            "    return lambda x: x * 2.0\n"
        )
        out = tmp_path / "double.jaxexp"
        out_info = export_model(str(src), str(out), platforms=("cpu",))
        assert out_info[0].shape == (2, 4)
        with SingleShot(model=str(out)) as s:
            (y,) = s.invoke([np.ones((2, 4), np.float32)])
        np.testing.assert_allclose(np.asarray(y), 2.0)

    def test_cli_export(self, tmp_path):
        from nnstreamer_tpu.cli import main

        src = tmp_path / "half.py"
        src.write_text(
            "def get_model():\n"
            "    return lambda x: x * 0.5\n"
        )
        out = tmp_path / "half.stablehlo"
        rc = main(["--export", str(src), str(out), "--platforms", "cpu",
                   "--input", "3:2", "--inputtype", "float32"])
        assert rc == 0
        with SingleShot(model=str(out)) as s:
            (y,) = s.invoke([np.full((2, 3), 4.0, np.float32)])
        np.testing.assert_allclose(np.asarray(y), 2.0)


class TestRejections:
    def test_savedmodel_pb_pointed_error(self, tmp_path, monkeypatch):
        """Without tensorflow importable, TF model paths get the
        offline-recipe error; with it, they go to in-process ingestion
        (tests/test_tf_backend.py)."""
        import nnstreamer_tpu.filters.tf_backend as tfb

        monkeypatch.setattr(tfb, "have_tensorflow", lambda: False)
        pb = tmp_path / "frozen.pb"
        pb.write_bytes(b"\x08\x01")
        with pytest.raises(ValueError, match="StableHLO"):
            SingleShot(framework="jax", model=str(pb))

    def test_savedmodel_dir_pointed_error(self, tmp_path, monkeypatch):
        import nnstreamer_tpu.filters.tf_backend as tfb

        monkeypatch.setattr(tfb, "have_tensorflow", lambda: False)
        d = tmp_path / "sm"
        d.mkdir()
        (d / "saved_model.pb").write_bytes(b"\x08\x01")
        with pytest.raises(ValueError, match="model-artifacts"):
            SingleShot(framework="jax", model=str(d))

    def test_garbage_artifact(self, tmp_path):
        bad = tmp_path / "bad.jaxexp"
        bad.write_bytes(b"not an artifact at all")
        with pytest.raises(Exception):
            SingleShot(framework="jax", model=str(bad))


def test_bench_artifact_mode(tmp_path, monkeypatch):
    """BENCH_ARTIFACT=1 runs the flagship pipeline from an exported
    artifact file (VERDICT r2 #1 done-criterion)."""
    import bench

    monkeypatch.setenv("BENCH_ARTIFACT", "1")
    monkeypatch.setattr(bench, "N_FRAMES", 16)
    monkeypatch.setattr(bench, "_ARTIFACT_CACHE", {})
    pipe = bench.build_pipeline(batch=8)
    outs = []
    pipe.get("sink").connect(lambda b: outs.append(b))
    msg = pipe.run(timeout=300)
    assert msg is not None and msg.kind == "eos"
    assert len(outs) == 2  # 16 frames / batch 8
    assert len(outs[0].meta["label_index"]) == 8
    filt = pipe.get("filter")
    assert str(filt.get_property("model")).endswith(".jaxexp")


def test_sharded_artifact_round_trip():
    """Multi-chip artifacts: a pjit'd fn exported with mesh shardings
    round-trips and its call distributes over a matching mesh (the
    conftest 8-device virtual CPU mesh stands in for a TPU slice)."""
    import jax
    import jax.export
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    w = jnp.ones((8, 16))
    sharded = jax.jit(lambda x: x @ w,
                      in_shardings=NamedSharding(mesh, P("dp", None)),
                      out_shardings=NamedSharding(mesh, P("dp", "tp")))
    exp = jax.export.export(sharded)(
        jax.ShapeDtypeStruct((4, 8), jnp.float32))
    assert exp.nr_devices == 4

    exp2 = jax.export.deserialize(bytes(exp.serialize()))
    x = jax.device_put(np.ones((4, 8), np.float32),
                       NamedSharding(mesh, P("dp", None)))
    out = exp2.call(x)
    assert float(np.asarray(out).sum()) == 4 * 16 * 8
    assert out.sharding.spec == P("dp", "tp")
