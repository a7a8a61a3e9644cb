"""Fleet launcher tests (serving/fleet.py): replica supervision, the
balanced client against real replica *processes*, crash restart, and
the rolling-restart continuity contract (checkpoint → kill → restore,
zero double-invokes via the restored dedup windows).
"""

import time

import numpy as np
import pytest

from nnstreamer_tpu.registry import ELEMENT, get_subplugin
from nnstreamer_tpu.serving.fleet import FleetLauncher
from nnstreamer_tpu.tensors.buffer import TensorBuffer


def _fleet_invokes(fleet):
    """All (instance:req_id) witness lines across the fleet's replica
    logs — each line is one actual worker invoke."""
    lines = []
    for i in range(fleet.replicas):
        p = fleet.state_dir / f"replica{i}" / "invokes.log"
        if p.exists():
            lines.extend(p.read_text().splitlines())
    return lines


def _client_for(fleet, operation, window=8):
    Client = get_subplugin(ELEMENT, "tensor_query_client")
    cl = Client(operation=operation, broker_port=fleet.broker_port,
                reliable=True, balance="shortest-slack",
                max_in_flight=window, timeout=5.0,
                discovery_stale_s=5.0)
    outs = []
    cl.srcpad.push = lambda b: outs.append(b)
    return cl, outs


def _send_range(cl, lo, hi):
    for i in range(lo, hi):
        cl.chain(cl.sinkpad, TensorBuffer(
            [np.full((4,), i, dtype=np.float32)], pts=i))


class TestFleetLauncher:
    def test_round_trip_balanced_exactly_once(self):
        fleet = FleetLauncher(replicas=2, operation="tf-rt", spin_ms=1.0,
                              log_invokes=True).start()
        try:
            eps = fleet.endpoints(timeout=20.0)
            assert len(eps) == 2
            assert fleet.replicas_up() == 2
            cl, outs = _client_for(fleet, "tf-rt")
            try:
                _send_range(cl, 0, 40)
                cl.handle_eos()
            finally:
                cl.stop()
            assert len(outs) == 40
            # in-order, byte-identical (echo doubles each value)
            assert [int(o.to_host().tensors[0][0]) for o in outs] == \
                [2 * i for i in range(40)]
            invokes = _fleet_invokes(fleet)
            assert len(invokes) == 40
            assert len(set(invokes)) == 40  # zero double-invokes
        finally:
            fleet.stop()

    def test_crash_restart_supervision(self):
        fleet = FleetLauncher(replicas=2, operation="tf-crash",
                              spin_ms=1.0).start()
        try:
            fleet.endpoints(timeout=20.0)
            fleet.kill_replica(0, graceful=False)
            assert fleet.replicas_up() == 1
            deadline = time.monotonic() + 20.0
            while fleet.replicas_up() < 2:
                assert time.monotonic() < deadline, \
                    "supervisor never relaunched the crashed replica"
                time.sleep(0.1)
        finally:
            fleet.stop()

    def test_rolling_restart_exactly_once(self):
        """The deploy contract: frames streamed across a rolling
        restart all arrive, in order, with every request invoked
        exactly once — the SIGTERM checkpoint carries each replica's
        dedup windows over to its successor (stable base_port keeps
        the endpoints, so the client's sticky reconnect replays into
        the restored windows)."""
        import socket as _socket

        with _socket.socket() as probe:
            probe.bind(("127.0.0.1", 0))
            base = probe.getsockname()[1] + 1000
        fleet = FleetLauncher(replicas=2, operation="tf-roll",
                              spin_ms=1.0, base_port=base,
                              log_invokes=True).start()
        try:
            fleet.endpoints(timeout=20.0)
            cl, outs = _client_for(fleet, "tf-roll", window=4)
            try:
                _send_range(cl, 0, 30)
                fleet.rolling_restart()
                _send_range(cl, 30, 60)
                cl.handle_eos()
            finally:
                cl.stop()
            assert len(outs) == 60
            assert [int(o.to_host().tensors[0][0]) for o in outs] == \
                [2 * i for i in range(60)]
            invokes = _fleet_invokes(fleet)
            assert len(set(invokes)) == len(invokes) == 60
        finally:
            fleet.stop()

    def test_replicas_validate(self):
        with pytest.raises(ValueError):
            FleetLauncher(replicas=0)


class TestOneProcessPerChip:
    """PR 21: the launcher hands every replica ITS cache directory (one
    rule, pipeline/continuity.py), refuses a --desc fleet larger than
    the host's chip count, and never initializes a JAX backend itself —
    the first process to do so owns the chip."""

    DESC = "tensor_query_serversrc operation=x ! tensor_query_serversink"

    @pytest.fixture
    def spawned_envs(self, monkeypatch):
        """Record what _spawn would start instead of starting it."""
        from nnstreamer_tpu.serving import fleet as fleet_mod

        seen = []

        class FakeProc:
            pid = 4242
            returncode = None

            def poll(self):
                return None

        def fake_popen(cmd, env=None, **kw):
            seen.append((cmd, env))
            return FakeProc()

        monkeypatch.setattr(fleet_mod.subprocess, "Popen", fake_popen)
        return seen

    def _spawn_one(self, tmp_path, **kw):
        from nnstreamer_tpu.serving.fleet import ReplicaHandle

        fleet = FleetLauncher(replicas=1, operation="tf-cache",
                              state_dir=str(tmp_path / "state"), **kw)
        h = ReplicaHandle(0, tmp_path / "state" / "replica0")
        h.state_dir.mkdir(parents=True)
        fleet._spawn(h)
        return fleet

    def test_children_get_the_parents_cache_directory(
            self, spawned_envs, tmp_path, monkeypatch):
        from nnstreamer_tpu.pipeline import continuity

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / "shared-cache"))
        self._spawn_one(tmp_path)
        _cmd, env = spawned_envs[-1]
        assert env["JAX_COMPILATION_CACHE_DIR"] == \
            str(tmp_path / "shared-cache") == \
            continuity.resolve_compile_cache_dir()
        assert "NNSTPU_COMPILE_CACHE" not in env

    def test_unset_children_get_checkout_jax_cache_not_the_state_dir(
            self, spawned_envs, tmp_path, monkeypatch):
        from nnstreamer_tpu.pipeline import continuity

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        fleet = self._spawn_one(tmp_path)
        _cmd, env = spawned_envs[-1]
        assert env["JAX_COMPILATION_CACHE_DIR"] == \
            continuity.DEFAULT_CACHE_DIR
        assert str(fleet.state_dir) not in env["JAX_COMPILATION_CACHE_DIR"]
        assert not (fleet.state_dir / "compile-cache").exists()

    def test_desc_fleet_larger_than_the_chip_count_is_refused(
            self, monkeypatch, tmp_path):
        from nnstreamer_tpu.serving import fleet as fleet_mod

        state = str(tmp_path)
        # the children would open the chips: JAX_PLATFORMS is not cpu.
        # No replica can be pinned to one chip yet, so as many replicas
        # as chips crash-loop just like more replicas than chips
        for chips in (1, 4):
            monkeypatch.setattr(fleet_mod, "tpu_chips_on_host",
                                lambda n=chips: n)
            for replicas in (2, 4, 5):
                with pytest.raises(
                        ValueError,
                        match=f"{replicas} --desc replicas.*{chips} TPU chip"):
                    FleetLauncher(replicas=replicas, desc=self.DESC,
                                  state_dir=state, env={"JAX_PLATFORMS": ""})
            # one replica is fine, and so is any number of replicas kept
            # on CPU XLA or of built-in echo replicas
            FleetLauncher(replicas=1, desc=self.DESC, state_dir=state,
                          env={"JAX_PLATFORMS": ""})
            FleetLauncher(replicas=3, desc=self.DESC, state_dir=state,
                          env={"JAX_PLATFORMS": "cpu"})
            FleetLauncher(replicas=3, state_dir=state,
                          env={"JAX_PLATFORMS": ""})

    def test_host_without_tpus_runs_any_desc_fleet(
            self, monkeypatch, tmp_path):
        """No chip, nothing to fight over: JAX runs the replicas on the
        CPU without JAX_PLATFORMS having to say so."""
        from nnstreamer_tpu.serving import fleet as fleet_mod

        assert fleet_mod.tpu_chips_on_host() >= 0  # the real scan runs
        monkeypatch.setattr(fleet_mod, "tpu_chips_on_host", lambda: 0)
        FleetLauncher(replicas=3, desc=self.DESC, state_dir=str(tmp_path),
                      env={"JAX_PLATFORMS": ""})

    def test_launcher_parent_stays_off_jax(self):
        """Importing the package and running a fleet from it initializes
        no JAX backend in the launcher process (a parent that touched JAX
        would hold the chip its replicas need)."""
        import os
        import subprocess
        import sys

        prog = (
            "import sys\n"
            "from nnstreamer_tpu.serving.fleet import FleetLauncher\n"
            "fleet = FleetLauncher(replicas=1, operation='tf-nojax',\n"
            "                      spin_ms=0.0).start()\n"
            "try:\n"
            "    assert len(fleet.endpoints(timeout=20.0)) == 1\n"
            "finally:\n"
            "    fleet.stop()\n"
            "backends = {}\n"
            "if 'jax' in sys.modules:\n"
            "    from jax._src import xla_bridge\n"
            "    backends = xla_bridge._backends\n"
            "assert not backends, backends\n"
            "print('jax imported:', 'jax' in sys.modules)\n")
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        proc = subprocess.run([sys.executable, "-c", prog], cwd=repo,
                              capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr
