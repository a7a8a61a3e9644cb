"""chip_smoke.py off the chip: it must refuse, and its own logic must hold.

The smoke itself only means something on the TPU (the builders run it
through their chip tool); tier-1 pins what would otherwise be found out
on chip time: that a CPU backend is REFUSED with no result, that the
pipeline strings the smoke states still parse and verify, and that the
script's checks pass against the real pipelines at cut sizes on CPU XLA
(``--rehearse-cpu``, run once here so that mode cannot rot either).
"""

import json
import os
import subprocess
import sys

import pytest

import nnstreamer_tpu as nt

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_refuses_a_cpu_backend_and_prints_no_result():
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"),
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "backend 'cpu'" in proc.stderr, proc.stderr
    assert proc.stdout.strip() == "", "a refused run printed a result"


def test_rehearsal_needs_the_cpu_said_out_loud():
    """--rehearse-cpu is explicit twice over: the flag AND
    JAX_PLATFORMS=cpu, so it can never take (or be mistaken for) the
    chip. The refusal comes before any backend is initialized."""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    proc = subprocess.run([sys.executable, "chip_smoke.py", "--rehearse-cpu"],
                          cwd=REPO, env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert "JAX_PLATFORMS=cpu" in proc.stderr, proc.stderr
    assert proc.stdout.strip() == ""


def test_rehearsal_runs_every_leg_on_the_cpu():
    """The same legs and checks at cut sizes, kernels interpreted, the
    mesh=dp4 leg on four of the eight virtual devices. It must never
    print ``"ok"``: that word is the chip's alone."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run([sys.executable, "chip_smoke.py", "--rehearse-cpu"],
                          cwd=REPO, env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert "ok" not in result and "rehearsal" in result
    assert result["device"] == {"platform": "cpu", "kind": "cpu", "count": 8}
    assert set(result["legs"]) == {"kernel", "stream", "server", "hybrid",
                                   "hybrid_delta"}
    assert result["legs"]["stream"]["staged_shards"] == [[0, 8]]
    assert result["mesh"]["spec"] == "dp4"
    assert result["mesh"]["staged_shards"] == [[i, 2] for i in range(4)]
    assert result["mesh"]["reshard_bytes"] == 0
    forward = result["legs"]["server"]["first_token_vs_forward"]
    assert forward["prompts"] == 3
    for name, state_bytes in (("hybrid", 3 * (8 * 64 * 128 * 4 + 3 * 768 * 2)),
                              ("hybrid_delta",
                               3 * (8 * 128 * 128 * 4 + 3 * 2048 * 2))):
        hybrid = result["legs"][name]
        assert hybrid["tokens_vs_forward"]["prompts"] == 2
        assert hybrid["tokens_vs_forward"]["tokens_each"] == 9
        assert hybrid["moe_tokens_held"] > 0
        assert hybrid["state_bytes"] == 4 * state_bytes  # four lanes
        assert hybrid["state_update"] == "reference"     # off a TPU
    assert [(c["rule"], c["head_block"])
            for c in result["legs"]["kernel"]["lane_state"]] \
        == [("mamba2", 16), ("gated_delta", 4)]
    assert result["compile_cache"]["dir"] is None and result["claim"] is None


def test_last_line_is_the_verdict_and_nothing_else(monkeypatch, capsys):
    """The driver reads the LAST stdout line and rejects it unless it is
    exactly {"ok", "device": {"platform", "kind", "count"}}; the summary
    (legs, cache counts, claim) goes on the line before. ``run`` is
    stubbed: what is pinned is what ``main`` prints around it."""
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    summary = {"device": device, "legs": {"kernel": {}}, "claim": None}
    monkeypatch.setattr(chip_smoke, "run", lambda rehearse: dict(summary))
    assert chip_smoke.main([]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert len(lines) == 2
    assert json.loads(lines[0]) == summary
    verdict = json.loads(lines[-1])
    assert verdict == {"ok": True, "device": device}
    assert list(verdict) == ["ok", "device"]
    assert list(verdict["device"]) == ["platform", "kind", "count"]
    assert isinstance(verdict["device"]["count"], int)
    # the rehearsal prints its summary and never the verdict
    assert chip_smoke.main(["--rehearse-cpu"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert [json.loads(line) for line in lines] == [summary]


@pytest.mark.parametrize("desc", [
    chip_smoke.STREAM_DESC.format(frames=8, model="m", mesh=""),
    chip_smoke.STREAM_DESC.format(frames=8, model="m", mesh="mesh=dp4 "),
    chip_smoke.FRAMES_DESC.format(frames=8),
    chip_smoke.SERVER_DESC.format(engine="e", max_new=4),
    chip_smoke.CLIENT_DESC.format(port=1),
], ids=["stream", "stream-dp4", "frames", "server", "client"])
def test_smoke_pipeline_strings_parse_and_verify(desc):
    pipe = nt.parse_launch(desc)  # a renamed property raises here
    assert pipe.verify() == []


def test_full_sizes_are_the_full_widths():
    """The argument-less run may cut depth, never width or the bucket
    coverage the issue asks of the server leg."""
    full = chip_smoke.FULL
    assert full["frames"] == 64 and full["lm_layers"] == 8
    lens = [n for client in full["prompts"] for n in client]
    buckets = set()
    for n in lens:
        b = 16
        while b < n:
            b *= 2
        buckets.add(b)
    assert len(buckets) >= 2 and max(buckets) >= 256
    assert (1, 256, 8, 64) in full["flash_shapes"]
    assert (4, 4096, 8, 64) in full["flash_shapes"]
