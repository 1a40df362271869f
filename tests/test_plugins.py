"""External plugin framework tests
(reference scenarios: plugins/drivers/testutils + drivermanager tests —
real subprocess plugins over the handshake protocol)."""

import os
import threading
import time

import pytest

from nomad_tpu import mock
from nomad_tpu.plugins import PluginError, PluginManager, launch_plugin

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PLUGDIR = os.path.join(REPO, "examples", "plugins")


@pytest.fixture(scope="module")
def manager(tmp_path_factory):
    m = PluginManager(PLUGDIR,
                      socket_dir=str(tmp_path_factory.mktemp("socks")))
    m.scan()
    yield m
    m.shutdown()


class TestProtocol:
    def test_handshake_and_info(self, manager):
        assert "hello" in manager.drivers
        assert "fake-gpu" in manager.devices

    def test_refuses_direct_execution(self):
        import subprocess
        import sys
        env = {k: v for k, v in os.environ.items()
               if not k.startswith("NOMAD_TPU_PLUGIN")}
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        p = subprocess.run(
            [sys.executable, os.path.join(PLUGDIR, "hello_driver.py")],
            capture_output=True, timeout=120, env=env)
        assert p.returncode == 1
        assert b"plugin manager" in p.stderr

    def test_bad_plugin_rejected(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text("import sys; sys.exit(3)\n")
        import sys
        with pytest.raises(PluginError):
            launch_plugin([sys.executable, str(bad)],
                          str(tmp_path / "socks"), timeout=60.0)


class TestExternalDriver:
    def test_task_lifecycle(self, manager):
        drv = manager.drivers["hello"]
        fp = drv.fingerprint()
        assert fp["driver.hello"] == "1"
        task = mock.job().task_groups[0].tasks[0]
        task.driver = "hello"
        task.config = {"message": "hi", "run_for_s": 0.2}
        h = drv.start_task("t1", task, {"NOMAD_TASK_NAME": "web"}, "")
        assert h.pid > 0
        res = drv.wait_task(h, timeout=60.0)
        assert res is not None and res.successful()

    def test_stop_task(self, manager):
        drv = manager.drivers["hello"]
        task = mock.job().task_groups[0].tasks[0]
        task.config = {"run_for_s": 300}
        h = drv.start_task("t2", task, {}, "")
        assert drv.recover_task(h)
        drv.stop_task(h, kill_timeout=2.0)
        res = drv.wait_task(h, timeout=60.0)
        assert res is not None

    def test_concurrent_wait_does_not_block_other_calls(self, manager):
        """Request-id multiplexing: a blocked wait_task must not stall
        fingerprints (the reason the reference multiplexes streams)."""
        drv = manager.drivers["hello"]
        task = mock.job().task_groups[0].tasks[0]
        task.config = {"run_for_s": 3}
        h = drv.start_task("t3", task, {}, "")
        import threading
        done = []
        t = threading.Thread(
            target=lambda: done.append(drv.wait_task(h, timeout=30)))
        t.start()
        t0 = time.time()
        fp = drv.fingerprint()
        assert fp and time.time() - t0 < 2.0
        drv.stop_task(h, 1.0)
        t.join(timeout=10)
        assert done


class TestSupervision:
    def test_crashed_plugin_relaunched(self, tmp_path_factory):
        # a SHORT socket dir: launch_plugin names the socket
        # plugin-<pid>-<thread>-<seq>.sock under it, and a unix socket
        # path holds 107 bytes.  Under xdist the per-test tmp_path
        # (.../popen-gwN/test_crashed_plugin_relaunched0/socks) went
        # past that, and the plugin died at bind before any relaunch
        m = PluginManager(PLUGDIR,
                          socket_dir=str(tmp_path_factory.mktemp("s")))
        m.scan()
        from nomad_tpu.core.logging import RING
        assert "hello" in m.drivers, RING.tail(6)
        try:
            drv = m.drivers["hello"]
            assert drv.fingerprint()
            # kill the plugin process behind the shim
            drv.client.proc.kill()
            drv.client.proc.wait(timeout=5)
            assert not drv.client.alive()
            assert drv.fingerprint() == {}      # dead connection
            # the supervisor's step is scan(); signal when its first
            # rescan (the relaunch and handshake, synchronous) returns,
            # so the test waits on that and polls nothing
            rescanned = threading.Event()
            scan = m.scan

            def scan_then_signal():
                scan()
                rescanned.set()

            m.scan = scan_then_signal
            m.start_supervisor(interval=0.05)
            # a bound, not a wait: two launch attempts of 60 s each
            assert rescanned.wait(timeout=150), RING.tail(6)
            # the SAME shim object works again after relaunch
            assert drv.fingerprint()["driver.hello"] == "1", RING.tail(6)
        finally:
            m.shutdown()


class TestExternalDevicePlugin:
    def test_fingerprint_groups(self, manager):
        groups = manager.fingerprint_devices()
        ids = {g.id() for g in groups}
        assert "acme/gpu/fake100" in ids

    def test_reserve(self, manager):
        plug = manager.devices["fake-gpu"]
        r = plug.reserve(["fake100-1"])
        assert r["envs"]["ACME_VISIBLE_DEVICES"] == "fake100-1"


class TestClientIntegration:
    def test_client_uses_plugin_driver_and_devices(self, tmp_path):
        """Full slice: client with plugin_dir schedules a job onto the
        external driver; node advertises the plugin's devices."""
        from nomad_tpu.core.server import Server
        from nomad_tpu.client.client import Client, InProcessRPC

        srv = Server(dev_mode=False, heartbeat_ttl=3600)
        srv.start()
        node = mock.node()
        cl = Client(InProcessRPC(srv), node=node,
                    data_dir=str(tmp_path / "c1"), plugin_dir=PLUGDIR)
        cl.start()
        try:
            nd = srv.state.node_by_id(node.id)
            assert nd.attributes.get("driver.hello") == "1"
            assert nd.drivers.get("hello") is True
            assert any(d.id() == "acme/gpu/fake100"
                       for d in nd.resources.devices)

            job = mock.job()
            job.id = "hello-job"
            job.task_groups[0].count = 1
            t = job.task_groups[0].tasks[0]
            t.driver = "hello"
            t.config = {"message": "external", "run_for_s": 60}
            from nomad_tpu.structs import RequestedDevice
            t.resources.devices = [RequestedDevice(name="gpu", count=1)]
            srv.register_job(job)
            deadline = time.time() + 20
            runner = None
            while time.time() < deadline:
                runners = list(cl.alloc_runners.values())
                if runners and runners[0].task_runners[0].state.state \
                        == "running":
                    runner = runners[0]
                    break
                time.sleep(0.2)
            assert runner is not None, "task never started on plugin driver"
            tr = runner.task_runners[0]
            assert tr.handle.driver == "hello"
            assert tr.handle.pid > 0
            # device plugin reserve() mapped the assigned instance into
            # the task env (plus the generic NOMAD_DEVICE_* exposure)
            alloc = runner.alloc
            assert alloc.allocated_devices
            iid = alloc.allocated_devices[0].device_ids[0]
            assert tr.env["ACME_VISIBLE_DEVICES"] == iid
            assert tr.env["NOMAD_DEVICE_ACME_GPU_FAKE100"] == iid
        finally:
            cl.shutdown()
            srv.shutdown()
