"""Launcher-layer unit tests.

Mirrors the reference's mock-based launcher testing strategy
(/root/reference/test/test_run.py, 41 tests: hostfile parsing, env
construction, controller selection — no cluster needed) plus live KV-store
and safe-exec coverage (test/test_service.py style).
"""

import os
import pickle
import subprocess
import sys
import textwrap
import threading
import time

import pytest

from horovod_tpu.runner import (HostInfo, get_host_assignments, parse_hostfile,
                                parse_hosts)
from horovod_tpu.runner import config_parser, launch
from horovod_tpu.runner.exec_run import (CHIP_BINDING_KEYS, _remote_command,
                                         is_local_host, slot_env)
from horovod_tpu.runner.rendezvous import (KVStoreClient, KVStoreServer,
                                           RendezvousServer)
from horovod_tpu.runner.safe_exec import safe_exec


# -- host parsing / assignment (reference test_run.py hosts tests) -----------
def test_parse_hosts():
    hosts = parse_hosts("h1:4,h2:2,h3")
    assert hosts == [HostInfo("h1", 4), HostInfo("h2", 2), HostInfo("h3", 1)]


def test_parse_hosts_rejects_garbage():
    with pytest.raises(ValueError):
        parse_hosts("h1:four")


def test_parse_hostfile(tmp_path):
    p = tmp_path / "hostfile"
    p.write_text("# comment\nh1 slots=4\nh2:2\nh3\n")
    assert parse_hostfile(str(p)) == [
        HostInfo("h1", 4), HostInfo("h2", 2), HostInfo("h3", 1)]


def test_host_assignments_ranks_and_cross():
    slots, size = get_host_assignments(
        [HostInfo("a", 2), HostInfo("b", 2)], 4)
    assert size == 4
    by_rank = {s.rank: s for s in slots}
    assert [by_rank[r].hostname for r in range(4)] == ["a", "a", "b", "b"]
    assert [by_rank[r].local_rank for r in range(4)] == [0, 1, 0, 1]
    # cross rank indexes hosts sharing the local_rank
    assert by_rank[0].cross_rank == 0 and by_rank[2].cross_rank == 1
    assert all(s.cross_size == 2 for s in slots)
    assert all(s.local_size == 2 for s in slots)


def test_host_assignments_ragged():
    slots, size = get_host_assignments(
        [HostInfo("a", 2), HostInfo("b", 1)], 3)
    by_rank = {s.rank: s for s in slots}
    # local_rank 1 exists only on host a
    assert by_rank[1].cross_size == 1 and by_rank[1].cross_rank == 0
    assert by_rank[2].hostname == "b" and by_rank[2].local_size == 1


def test_host_assignments_insufficient_slots():
    with pytest.raises(ValueError):
        get_host_assignments([HostInfo("a", 1)], 2)


def test_host_assignments_max_np():
    slots, size = get_host_assignments(
        [HostInfo("a", 4), HostInfo("b", 4)], 2, max_np=6)
    assert size == 6
    assert sum(1 for s in slots if s.hostname == "a") == 4


# -- env contract ------------------------------------------------------------
def test_slot_env_contract():
    slots, _ = get_host_assignments([HostInfo("localhost", 2)], 2)
    env = slot_env(slots[1], "127.0.0.1:7777", "127.0.0.1", 8888,
                   base_env={})
    assert env["HVD_TPU_RANK"] == "1"
    assert env["HVD_TPU_SIZE"] == "2"
    assert env["HVD_TPU_LOCAL_RANK"] == "1"
    assert env["HVD_TPU_COORDINATOR_ADDR"] == "127.0.0.1:7777"
    assert env["HVD_TPU_RENDEZVOUS_PORT"] == "8888"


# -- one process per chip ----------------------------------------------------
def test_four_local_slots_get_four_disjoint_chips():
    slots, _ = get_host_assignments([HostInfo("localhost", 4)], 4)
    envs = [slot_env(s, "127.0.0.1:7777", base_env={}) for s in slots]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert [e["CLOUD_TPU_TASK_ID"] for e in envs] == ["0", "1", "2", "3"]
    assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 4
    for e in envs:
        # one chip each, out of the host's 2x2 grid; every rank knows
        # where all four runtimes listen, its own port among them
        assert e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert e["TPU_PROCESS_BOUNDS"] == "2,2,1"
        addresses = e["TPU_PROCESS_ADDRESSES"].split(",")
        assert len(set(addresses)) == 4
        assert f"localhost:{e['TPU_PROCESS_PORT']}" in addresses


def test_one_slot_per_host_is_left_every_chip():
    for hosts in ([HostInfo("localhost", 1)],
                  [HostInfo("a", 1), HostInfo("b", 1)]):
        slots, _ = get_host_assignments(hosts, len(hosts))
        for s in slots:
            env = slot_env(s, "127.0.0.1:7777", base_env={})
            assert not set(env) & set(CHIP_BINDING_KEYS)


def test_layouts_with_no_known_chip_grid_are_left_unbound():
    """Two or three slots, or several multi-slot hosts: no binding here —
    hvd.init() refuses those on a TPU host (test_basics)."""
    for hosts, n in (([HostInfo("localhost", 3)], 3),
                     ([HostInfo("a", 4), HostInfo("b", 4)], 8)):
        slots, _ = get_host_assignments(hosts, n)
        env = slot_env(slots[-1], "127.0.0.1:7777", base_env={})
        assert not set(env) & set(CHIP_BINDING_KEYS)


def test_remote_command_forwards_the_chip_binding():
    slots, _ = get_host_assignments([HostInfo("tpu-host", 4)], 4)
    env = slot_env(slots[2], "tpu-host:7777", base_env={"HOME": "/root"})
    remote = _remote_command(["python", "train.py"], env, "tpu-host",
                             ("PATH",))[-1]
    for key in CHIP_BINDING_KEYS:
        assert f"{key}={env[key]}" in remote.replace("'", "")
    assert "HVD_TPU_RANK=2" in remote and "HOME=" not in remote


def test_is_local_host():
    assert is_local_host("localhost")
    assert is_local_host("127.0.0.1")
    assert not is_local_host("tpu-worker-7.example.com")


# -- CLI arg -> env translation (reference config_parser tests) --------------
def test_set_env_from_args():
    args = launch.parse_args(
        ["--fusion-threshold-mb", "32", "--timeline-filename", "/tmp/t.json",
         "--autotune", "--check-consistency", "--", "python", "x.py"])
    env = config_parser.set_env_from_args({}, args)
    assert env["HVD_TPU_FUSION_THRESHOLD"] == str(32 * 1024 * 1024)
    assert env["HVD_TPU_TIMELINE"] == "/tmp/t.json"
    assert env["HVD_TPU_AUTOTUNE"] == "1"
    assert env["HVD_TPU_CHECK_CONSISTENCY"] == "1"
    assert args.command == ["python", "x.py"]


def test_config_file_merge(tmp_path):
    cfg = tmp_path / "cfg.yaml"
    cfg.write_text(textwrap.dedent("""
        autotune: true
        timeline:
          filename: /tmp/tl.json
        stall_check:
          warning_time_seconds: 10
    """))
    args = launch.parse_args(
        ["--config-file", str(cfg), "python", "x.py"])
    assert args.autotune is True
    assert args.timeline_filename == "/tmp/tl.json"
    assert args.stall_check_warning_time_seconds == 10


def test_elastic_dispatch_detection(monkeypatch):
    called = {}

    def fake_elastic(args):
        called["elastic"] = True
        return 0

    monkeypatch.setattr(launch, "_run_elastic", fake_elastic)
    launch.run_commandline(
        ["--host-discovery-script", "/bin/discover", "python", "x.py"])
    assert called.get("elastic")


# -- KV store ----------------------------------------------------------------
def test_kvstore_put_get_wait_delete():
    server = KVStoreServer()
    port = server.start()
    try:
        client = KVStoreClient("127.0.0.1", port)
        assert client.get("s", "missing") is None
        client.put("s", "k", b"hello")
        assert client.get("s", "k") == b"hello"

        def delayed_put():
            time.sleep(0.3)
            client.put("s", "later", b"arrived")

        t = threading.Thread(target=delayed_put)
        t.start()
        assert client.wait("s", "later", timeout=5) == b"arrived"
        t.join()
        client.delete("s", "k")
        assert client.get("s", "k") is None
        with pytest.raises(TimeoutError):
            client.wait("s", "never", timeout=0.3)
    finally:
        server.stop()


def test_rendezvous_publishes_rank_and_size():
    slots, _ = get_host_assignments([HostInfo("nodeA", 2)], 2)
    server = RendezvousServer()
    port = server.start()
    try:
        server.init(slots)
        client = KVStoreClient("127.0.0.1", port)
        blob = client.get("rank_and_size", "nodeA:1")
        rank, size, lr, ls, cr, cs = map(int, blob.decode().split(","))
        assert (rank, size, lr, ls) == (1, 2, 1, 2)
    finally:
        server.stop()


def test_kvstore_dynamic_handler():
    server = KVStoreServer(handlers={"live": lambda k: f"dyn:{k}".encode()})
    port = server.start()
    try:
        client = KVStoreClient("127.0.0.1", port)
        assert client.get("live", "abc") == b"dyn:abc"
    finally:
        server.stop()


# -- safe exec ---------------------------------------------------------------
def test_safe_exec_captures_output(capfd):
    code = safe_exec([sys.executable, "-c", "print('marker-xyz')"],
                     stdout_prefix="[0]<stdout> ")
    assert code == 0
    out = capfd.readouterr().out
    assert "[0]<stdout> marker-xyz" in out


def test_safe_exec_kills_process_tree():
    stop = threading.Event()
    # child spawns a grandchild; both must die when stop fires
    script = ("import subprocess,sys,time;"
              "subprocess.Popen([sys.executable,'-c','import time;"
              "time.sleep(60)']);time.sleep(60)")
    result = {}

    def target():
        result["code"] = safe_exec([sys.executable, "-c", script],
                                   stop_event=stop)

    t = threading.Thread(target=target)
    t.start()
    time.sleep(0.8)
    stop.set()
    t.join(timeout=15)
    assert not t.is_alive()
    assert result["code"] != 0


# -- end-to-end local launch (no jax needed in workers) ----------------------
@pytest.mark.integration
def test_cli_static_launch_end_to_end(tmp_path):
    out = tmp_path / "logs"
    script = tmp_path / "worker.py"
    script.write_text(
        "import os\n"
        "print('rank', os.environ['HVD_TPU_RANK'],"
        " 'of', os.environ['HVD_TPU_SIZE'])\n")
    rc = launch.run_commandline(
        ["-np", "2", "--output-filename", str(out), "--",
         sys.executable, str(script)])
    assert rc == 0
    logs = sorted(p.name for p in out.iterdir())
    assert logs == ["rank.0.log", "rank.1.log"]
    assert "rank 0 of 2" in (out / "rank.0.log").read_text()


@pytest.mark.integration
def test_cli_propagates_failure(tmp_path):
    rc = launch.run_commandline(
        ["-np", "2", "--", sys.executable, "-c", "import sys; sys.exit(3)"])
    assert rc == 3


@pytest.mark.integration
def test_programmatic_run_api():
    from horovod_tpu.runner import run

    def fn(mult):
        import os
        return int(os.environ["HVD_TPU_RANK"]) * mult

    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    results = run(fn, args=(10,), np=2, env=env)
    assert results == [0, 10]


@pytest.mark.integration
def test_programmatic_run_api_propagates_exception():
    from horovod_tpu.runner import run

    def fn():
        raise ValueError("boom-unique")

    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    with pytest.raises(RuntimeError, match="boom-unique"):
        run(fn, np=2, env=env)


# ---------------------------------------------------------------------------
# round 3: driver/task services with NIC intersection + LSF/jsrun
# (reference: runner/driver/driver_service.py:135-204, runner/js_run.py:146)
# ---------------------------------------------------------------------------
class TestDriverTaskServices:
    def test_register_and_intersect(self):
        from horovod_tpu.runner.driver_service import (
            DriverClient, DriverService, TaskService, get_common_interfaces)
        from horovod_tpu.runner.network import make_secret_key

        key = make_secret_key()
        driver = DriverService(num_tasks=3, key=key)
        tasks = [TaskService(i, key) for i in range(3)]
        try:
            client = DriverClient(
                {"lo": [("127.0.0.1", driver.port)]}, key)
            for i, t in enumerate(tasks):
                # every task advertises a working loopback interface plus
                # a dead "mgmt" interface that must not survive the
                # intersection (the mocked-unroutable-NIC scenario)
                client.register(i, {
                    "lo": [("127.0.0.1", t.port)],
                    "mgmt": [("10.255.255.250", 1)],
                })
            assert client.all_registered()
            assert driver.wait_for_all(timeout=5)
            common, filtered = get_common_interfaces(
                driver, key, probe_timeout=1.0)
            assert common == {"lo"}
            for i in range(3):
                assert set(filtered[i]) == {"lo"}
        finally:
            driver.shutdown()
            for t in tasks:
                t.shutdown()

    def test_unregistered_not_done(self):
        from horovod_tpu.runner.driver_service import (
            DriverClient, DriverService)
        from horovod_tpu.runner.network import make_secret_key

        key = make_secret_key()
        driver = DriverService(num_tasks=2, key=key)
        try:
            client = DriverClient(
                {"lo": [("127.0.0.1", driver.port)]}, key)
            client.register(0, {"lo": [("127.0.0.1", 1)]})
            assert not client.all_registered()
            assert client.task_addresses(1) is None
        finally:
            driver.shutdown()


class TestLSF:
    def test_compute_hosts_from_hostfile(self, tmp_path, monkeypatch):
        from horovod_tpu.runner.lsf import LSFUtils
        hf = tmp_path / "hosts"
        hf.write_text("batch1\nnode1\nnode1\nnode2\nnode2\n")
        monkeypatch.setenv("LSB_JOBID", "123")
        monkeypatch.setenv("LSB_DJOB_HOSTFILE", str(hf))
        assert LSFUtils.using_lsf()
        assert LSFUtils.get_compute_hosts() == [("node1", 2), ("node2", 2)]
        assert LSFUtils.get_num_processes() == 4
        assert LSFUtils.get_num_hosts() == 2

    def test_compute_hosts_from_mcpu(self, monkeypatch):
        from horovod_tpu.runner.lsf import LSFUtils
        monkeypatch.delenv("LSB_DJOB_HOSTFILE", raising=False)
        monkeypatch.setenv("LSB_MCPU_HOSTS", "batch1 1 node1 4 node2 4")
        assert LSFUtils.get_compute_hosts() == [("node1", 4), ("node2", 4)]

    def test_jsrun_command_shape(self, monkeypatch):
        from horovod_tpu.runner.lsf import make_jsrun_command
        monkeypatch.delenv("LSB_JOBID", raising=False)
        cmd = make_jsrun_command(
            ["python", "train.py"],
            {"HVD_TPU_SIZE": "8", "PYTHONPATH": "/x", "SECRET": "no"},
            num_proc=8, num_hosts=2)
        assert cmd[0] == "jsrun"
        assert cmd[cmd.index("--nrs") + 1] == "8"
        assert cmd[cmd.index("--tasks_per_rs") + 1] == "1"
        assert cmd[cmd.index("--rs_per_host") + 1] == "4"
        assert "-E" in cmd and "HVD_TPU_SIZE=8" in cmd
        assert "PYTHONPATH=/x" in cmd
        assert "SECRET=no" not in cmd          # only contract env forwarded
        assert cmd[-2:] == ["python", "train.py"]

    def test_jsrun_rank_env_mapping(self):
        from horovod_tpu.runner.lsf import jsrun_rank_env
        env = {"PMIX_RANK": "3", "JSM_NAMESPACE_SIZE": "8",
               "JSM_NAMESPACE_LOCAL_RANK": "1",
               "JSM_NAMESPACE_LOCAL_SIZE": "4"}
        out = jsrun_rank_env(env)
        assert out == {"HVD_TPU_RANK": "3", "HVD_TPU_SIZE": "8",
                       "HVD_TPU_LOCAL_RANK": "1", "HVD_TPU_LOCAL_SIZE": "4",
                       "HVD_TPU_CROSS_RANK": "0", "HVD_TPU_CROSS_SIZE": "2"}
        # OMPI fallbacks
        out = jsrun_rank_env({"OMPI_COMM_WORLD_RANK": "0",
                              "OMPI_COMM_WORLD_SIZE": "2"})
        assert out["HVD_TPU_RANK"] == "0" and out["HVD_TPU_SIZE"] == "2"

    def test_resolve_hosts_defaults_to_lsf(self, tmp_path, monkeypatch):
        from horovod_tpu.runner import launch
        hf = tmp_path / "hosts"
        hf.write_text("batch1\nnodeA\nnodeA\nnodeB\n")
        monkeypatch.setenv("LSB_JOBID", "7")
        monkeypatch.setenv("LSB_DJOB_HOSTFILE", str(hf))
        args = launch.parse_args(["-np", "3", "--", "python", "x.py"])
        hosts = launch._resolve_hosts(args)
        assert [(h.hostname, h.slots) for h in hosts] == \
            [("nodeA", 2), ("nodeB", 1)]

    def test_launcher_jsrun_selected(self, monkeypatch):
        """--launcher jsrun routes to _run_jsrun (mocked). Outside an LSF
        job this is an error (reference run_controller launch.py:645-651),
        so simulate the allocation."""
        from horovod_tpu.runner import launch
        monkeypatch.setenv("LSB_JOBID", "123")
        called = {}
        monkeypatch.setattr(launch, "_run_jsrun",
                            lambda args: called.setdefault("jsrun", 0) or 0)
        rc = launch.run_commandline(
            ["--launcher", "jsrun", "-np", "2", "--", "python", "x.py"])
        assert rc == 0 and "jsrun" in called


def test_check_build_matrix(capsys):
    """--check-build prints the availability matrix and exits 0
    (reference: horovodrun --check-build, launch.py:110)."""
    from horovod_tpu.runner import launch
    rc = launch.run_commandline(["--check-build"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "horovod_tpu v" in out
    assert "JAX / Flax (native plane)" in out
    assert "XLA collectives" in out
