"""mpirun launch-path tests (reference: /root/reference/test/test_run.py's
mpi_run suite — mock the implementation probe and the spawn, assert the
assembled command line)."""

import os

import pytest

from horovod_tpu.runner import launch as launch_mod
from horovod_tpu.runner.mpi_run import (
    MISSING_IMPL, MPICH_IMPL, MPISettings, OPENMPI_IMPL, SPECTRUM_IMPL,
    UNKNOWN_IMPL, coordinator_addr_for, get_mpi_implementation,
    is_exportable, mpi_available, mpi_run, mpi_run_command)

OMPI_OUT = "mpirun (Open MPI) 4.1.4\n"
SMPI_OUT = "mpirun (IBM Spectrum MPI) 10.3.0.0\n"
MPICH_OUT = "HYDRA build details:\n    Version: MPICH 4.0\n"


def exec_returning(out, code=0):
    def fn(cmd):
        assert cmd == ["mpirun", "--version"]
        return (out, code)
    return fn


class TestDetection:
    def test_openmpi(self):
        assert get_mpi_implementation(exec_returning(OMPI_OUT)) == OPENMPI_IMPL

    def test_openrte_counts_as_openmpi(self):
        assert get_mpi_implementation(
            exec_returning("OpenRTE 3.1\n")) == OPENMPI_IMPL

    def test_spectrum(self):
        assert get_mpi_implementation(
            exec_returning(SMPI_OUT)) == SPECTRUM_IMPL

    def test_mpich(self):
        assert get_mpi_implementation(exec_returning(MPICH_OUT)) == MPICH_IMPL

    def test_unknown(self):
        assert get_mpi_implementation(
            exec_returning("SomeVendor MPI 1.0")) == UNKNOWN_IMPL

    def test_missing(self):
        assert get_mpi_implementation(
            exec_returning("not found", 127)) == MISSING_IMPL

    def test_available(self):
        assert mpi_available(exec_returning(OMPI_OUT))
        assert not mpi_available(exec_returning("x", 1))
        assert not mpi_available(exec_returning("SomeVendor MPI"))


class TestExportable:
    @pytest.mark.parametrize("name", [
        "HVD_TPU_SIZE", "HOROVOD_LOG_LEVEL", "PATH", "LD_LIBRARY_PATH",
        "JAX_PLATFORMS"])
    def test_yes(self, name):
        assert is_exportable(name)

    @pytest.mark.parametrize("name", [
        "OMPI_COMM_WORLD_RANK", "PMIX_RANK", "PMI_SIZE", "SLURM_PROCID",
        "BASH_FUNC_module%%", "OLDPWD", "PWD", "SHLVL", "_", ""])
    def test_no(self, name):
        assert not is_exportable(name)


def basic_settings(**kw):
    defaults = dict(num_proc=4, hosts="a:2,b:2")
    defaults.update(kw)
    return MPISettings(**defaults)


class TestCommandAssembly:
    def test_openmpi_basic(self):
        cmd = mpi_run_command(
            basic_settings(), {"HVD_TPU_SIZE": "4"},
            ["python", "train.py"], impl=OPENMPI_IMPL)
        assert cmd[0] == "mpirun"
        assert "--allow-run-as-root" in cmd and "--tag-output" in cmd
        i = cmd.index("-np")
        assert cmd[i + 1] == "4"
        i = cmd.index("-H")
        assert cmd[i + 1] == "a:2,b:2"
        # stability + binding defaults
        joined = " ".join(cmd)
        assert "-mca pml ob1" in joined and "-mca btl ^openib" in joined
        assert "-bind-to none" in joined and "-map-by slot" in joined
        # env passthrough and the worker command at the tail
        i = cmd.index("-x")
        assert cmd[i + 1] == "HVD_TPU_SIZE"
        assert cmd[-2:] == ["python", "train.py"]

    def test_env_sorted_and_filtered(self):
        env = {"ZZZ": "1", "AAA": "2", "OMPI_COMM_WORLD_RANK": "0",
               "BASH_FUNC_f%%": "() {:;}"}
        cmd = mpi_run_command(basic_settings(), env, ["c"],
                              impl=OPENMPI_IMPL)
        xs = [cmd[i + 1] for i, a in enumerate(cmd) if a == "-x"]
        assert xs == ["AAA", "ZZZ"]

    def test_mpich_uses_genvlist_and_hosts(self):
        cmd = mpi_run_command(
            basic_settings(), {"B": "1", "A": "2"}, ["c"], impl=MPICH_IMPL)
        assert "-x" not in cmd
        i = cmd.index("-genvlist")
        assert cmd[i + 1] == "A,B"
        i = cmd.index("-hosts")
        assert cmd[i + 1] == "a:2,b:2"
        assert "-prepend-rank" in cmd
        assert "--allow-run-as-root" not in cmd

    def test_spectrum_binding_and_tcp(self):
        cmd = mpi_run_command(
            basic_settings(tcp_flag=True), {}, ["c"], impl=SPECTRUM_IMPL)
        joined = " ".join(cmd)
        assert "-tcp" in cmd
        assert "-bind-to socket" in joined and "-rank-by core" in joined
        cmd = mpi_run_command(
            basic_settings(tcp_flag=False), {}, ["c"], impl=SPECTRUM_IMPL)
        assert "-tcp" not in cmd

    def test_ssh_port(self):
        cmd = mpi_run_command(
            basic_settings(ssh_port=2222), {}, ["c"], impl=OPENMPI_IMPL)
        i = cmd.index("plm_rsh_args")
        assert cmd[i + 1] == "-p 2222"

    def test_nics(self):
        cmd = mpi_run_command(
            basic_settings(nics=("eth0", "eth1")), {}, ["c"],
            impl=OPENMPI_IMPL)
        joined = " ".join(cmd)
        assert "-mca btl_tcp_if_include eth0,eth1" in joined
        assert "-mca oob_tcp_if_include eth0,eth1" in joined
        # no NCCL plumbing in this stack
        assert "NCCL_SOCKET_IFNAME" not in joined

    def test_output_filename(self):
        cmd = mpi_run_command(
            basic_settings(output_filename="/tmp/logs"), {}, ["c"],
            impl=OPENMPI_IMPL)
        i = cmd.index("--output-filename")
        assert cmd[i + 1] == "/tmp/logs"

    def test_extra_mpi_args(self):
        cmd = mpi_run_command(
            basic_settings(extra_mpi_args="-mca orte_base_help_aggregate 0"),
            {}, ["c"], impl=OPENMPI_IMPL)
        joined = " ".join(cmd)
        assert "-mca orte_base_help_aggregate 0" in joined

    def test_binding_override(self):
        cmd = mpi_run_command(
            basic_settings(binding_args="-bind-to core"), {}, ["c"],
            impl=OPENMPI_IMPL)
        joined = " ".join(cmd)
        assert "-bind-to core" in joined and "-bind-to none" not in joined

    def test_large_cluster_flags(self):
        hosts = ",".join(f"h{i}:1" for i in range(64))
        cmd = mpi_run_command(
            MPISettings(num_proc=64, hosts=hosts), {}, ["c"],
            impl=OPENMPI_IMPL)
        joined = " ".join(cmd)
        assert "plm_rsh_no_tree_spawn true" in joined
        assert "plm_rsh_num_concurrent 64" in joined

    def test_small_cluster_no_flags(self):
        cmd = mpi_run_command(basic_settings(), {}, ["c"], impl=OPENMPI_IMPL)
        assert "plm_rsh_no_tree_spawn" not in cmd

    def test_missing_impl_raises(self):
        with pytest.raises(RuntimeError, match="mpirun"):
            mpi_run_command(basic_settings(), {}, ["c"],
                            exec_fn=exec_returning("nope", 127))

    def test_unknown_impl_raises(self):
        with pytest.raises(RuntimeError, match="mpirun"):
            mpi_run_command(basic_settings(), {}, ["c"],
                            exec_fn=exec_returning("FooMPI 9.9"))


class TestCoordinatorAddr:
    def test_on_first_host_stable_port(self):
        a1 = coordinator_addr_for("a:2,b:2", seed="job1")
        a2 = coordinator_addr_for("a:2,b:2", seed="job1")
        assert a1 == a2 and a1.startswith("a:")
        port = int(a1.split(":")[1])
        assert 61000 <= port < 65500

    def test_distinct_jobs_distinct_ports(self):
        p1 = int(coordinator_addr_for("a:1", seed="j1").split(":")[1])
        p2 = int(coordinator_addr_for("a:1", seed="j2").split(":")[1])
        assert p1 != p2


class TestMpiRun:
    def test_injects_contract_and_spawns(self):
        captured = {}

        def spawn(argv, env):
            captured["argv"] = argv
            captured["env"] = env
            return 0

        rc = mpi_run(basic_settings(), {"MYVAR": "v"}, ["python", "t.py"],
                     exec_fn=exec_returning(OMPI_OUT), spawn_fn=spawn)
        assert rc == 0
        env = captured["env"]
        assert env["HVD_TPU_SIZE"] == "4"
        assert env["HVD_TPU_COORDINATOR_ADDR"].startswith("a:")
        # contract vars are forwarded on the command line too
        xs = [captured["argv"][i + 1]
              for i, a in enumerate(captured["argv"]) if a == "-x"]
        assert "HVD_TPU_COORDINATOR_ADDR" in xs and "HVD_TPU_SIZE" in xs
        assert "PATH" in captured["env"]  # driver PATH for mpirun itself

    def test_propagates_exit_code(self):
        rc = mpi_run(basic_settings(), {}, ["c"],
                     exec_fn=exec_returning(OMPI_OUT),
                     spawn_fn=lambda argv, env: 3)
        assert rc == 3


class TestReviewFixes:
    """Regressions from the round-5 code review of this module."""

    def test_mpich_family_rank_identity(self):
        """Hydra-launched workers (PMI_RANK/PMI_SIZE) resolve identity —
        without this the whole MPICH branch is dead weight."""
        from horovod_tpu.config import mpi_task_identity
        env = {"PMI_RANK": "3", "PMI_SIZE": "4", "MPI_LOCALRANKID": "1",
               "MPI_LOCALNRANKS": "2"}
        ident = mpi_task_identity(env)
        assert ident["RANK"] == 3 and ident["SIZE"] == 4
        assert ident["LOCAL_RANK"] == 1 and ident["LOCAL_SIZE"] == 2

    def test_cross_identity_derived_for_uniform_hosts(self):
        """MPI launchers export no cross-host identity; with uniform
        slots it is derivable from rank//local_size — without this,
        --mpi workers on multi-slot hosts get cross_rank==rank (wrong
        hierarchical grouping)."""
        from horovod_tpu.config import mpi_task_identity
        env = {"OMPI_COMM_WORLD_RANK": "3", "OMPI_COMM_WORLD_SIZE": "4",
               "OMPI_COMM_WORLD_LOCAL_RANK": "1",
               "OMPI_COMM_WORLD_LOCAL_SIZE": "2"}
        ident = mpi_task_identity(env)
        assert ident["CROSS_RANK"] == 1 and ident["CROSS_SIZE"] == 2
        # non-uniform (size not divisible): no guess
        env["OMPI_COMM_WORLD_SIZE"] = "5"
        ident = mpi_task_identity(env)
        assert "CROSS_RANK" not in ident

    def test_cross_identity_reaches_basics(self, monkeypatch):
        """End to end through Config.get: a worker env as mpirun sets it
        resolves the full GLOBAL/LOCAL/CROSS triple."""
        import horovod_tpu as hvd
        for k, v in (("OMPI_COMM_WORLD_RANK", "0"),
                     ("OMPI_COMM_WORLD_SIZE", "1"),
                     ("OMPI_COMM_WORLD_LOCAL_RANK", "0"),
                     ("OMPI_COMM_WORLD_LOCAL_SIZE", "1")):
            monkeypatch.setenv(k, v)
        for k in ("HVD_TPU_RANK", "HVD_TPU_SIZE", "HVD_TPU_LOCAL_RANK",
                  "HVD_TPU_LOCAL_SIZE", "HVD_TPU_CROSS_RANK",
                  "HVD_TPU_CROSS_SIZE"):
            monkeypatch.delenv(k, raising=False)
        if hvd.is_initialized():
            hvd.shutdown()
        hvd.init()
        try:
            assert hvd.cross_rank() == 0 and hvd.cross_size() == 1
            assert hvd.local_rank() == 0 and hvd.local_size() == 1
        finally:
            hvd.shutdown()

    def test_np_overrides_stale_size_env(self):
        captured = {}
        mpi_run(basic_settings(num_proc=4),
                {"HVD_TPU_SIZE": "8", "HVD_TPU_RANK": "0"}, ["c"],
                exec_fn=exec_returning(OMPI_OUT),
                spawn_fn=lambda argv, env: captured.update(env=env) or 0)
        assert captured["env"]["HVD_TPU_SIZE"] == "4"
        # stale per-process identity must not be forwarded
        assert "HVD_TPU_RANK" not in captured["env"]

    def test_mpich_ssh_port_warns(self, capsys):
        cmd = mpi_run_command(
            basic_settings(ssh_port=2222), {}, ["c"], impl=MPICH_IMPL)
        assert "plm_rsh_args" not in cmd
        assert "--ssh-port" in capsys.readouterr().err

    def test_mpich_nics_and_output_mapped(self):
        cmd = mpi_run_command(
            basic_settings(nics=("eth0",), output_filename="/tmp/l"),
            {}, ["c"], impl=MPICH_IMPL)
        assert cmd[cmd.index("-iface") + 1] == "eth0"
        assert "-outfile-pattern" in cmd

    def test_elastic_plus_mpi_rejected(self):
        with pytest.raises(RuntimeError, match="elastic"):
            launch_mod.run_commandline(
                ["--mpi", "--min-np", "2", "-np", "2", "-H", "a:1,b:1",
                 "--host-discovery-script", "/bin/true", "cmd"])

    def test_mpi_path_runs_ssh_precheck(self, monkeypatch):
        import horovod_tpu.runner.mpi_run as mr
        monkeypatch.setattr(mr, "_default_exec", exec_returning(OMPI_OUT))
        seen = {}

        def fake_check_ssh(hostnames, timeout=10.0, port=None):
            seen["hosts"] = sorted(hostnames)
            seen["port"] = port
            return ["unreachable-host"]

        monkeypatch.setattr(launch_mod, "check_ssh", fake_check_ssh)
        with pytest.raises(RuntimeError, match="ssh"):
            launch_mod.run_commandline(
                ["--mpi", "-np", "2", "-H", "a:1,b:1",
                 "--ssh-port", "2222", "cmd"])
        assert seen == {"hosts": ["a", "b"], "port": 2222}


class TestCLIIntegration:
    """horovodrun-tpu --mpi -np 4 -H a:2,b:2 cmd builds the right mpirun
    command (VERDICT r4 acceptance criterion)."""

    def _run(self, argv, monkeypatch, impl_out=OMPI_OUT):
        import horovod_tpu.runner.mpi_run as mr
        captured = {}
        argv = ["--disable-ssh-check"] + argv
        monkeypatch.setattr(mr, "_default_exec", exec_returning(impl_out))

        def fake_subprocess_run(cmd, env=None, **kw):
            captured["argv"] = cmd
            captured["env"] = env

            class R:
                returncode = 0
            return R()

        monkeypatch.setattr(mr.subprocess, "run", fake_subprocess_run)
        rc = launch_mod.run_commandline(argv)
        return rc, captured

    def test_mpi_flag(self, monkeypatch):
        rc, cap = self._run(
            ["--mpi", "-np", "4", "-H", "a:2,b:2", "python", "train.py"],
            monkeypatch)
        assert rc == 0
        argv = cap["argv"]
        assert argv[0] == "mpirun"
        assert argv[argv.index("-np") + 1] == "4"
        assert argv[argv.index("-H") + 1] == "a:2,b:2"
        assert argv[-2:] == ["python", "train.py"]
        assert cap["env"]["HVD_TPU_SIZE"] == "4"

    def test_launcher_mpi(self, monkeypatch):
        rc, cap = self._run(
            ["--launcher", "mpi", "-np", "2", "-H", "a:1,b:1", "cmd"],
            monkeypatch)
        assert rc == 0 and cap["argv"][0] == "mpirun"

    def test_mpi_args_passthrough(self, monkeypatch):
        rc, cap = self._run(
            ["--mpi", "-np", "2", "-H", "a:1,b:1",
             "--mpi-args", "-mca foo bar", "cmd"], monkeypatch)
        assert "-mca foo bar" in " ".join(cap["argv"])

    def test_env_contract_from_cli_args(self, monkeypatch):
        rc, cap = self._run(
            ["--mpi", "-np", "2", "-H", "a:1,b:1",
             "--fusion-threshold-mb", "32", "cmd"], monkeypatch)
        assert cap["env"].get("HVD_TPU_FUSION_THRESHOLD") is not None

    def test_mpi_missing_errors(self, monkeypatch):
        import horovod_tpu.runner.mpi_run as mr
        monkeypatch.setattr(mr, "_default_exec",
                            exec_returning("not found", 127))
        with pytest.raises(RuntimeError, match="mpirun"):
            launch_mod.run_commandline(
                ["--mpi", "-np", "2", "-H", "a:1,b:1", "cmd"])

    def test_gloo_flag_forces_local(self, monkeypatch):
        called = {}
        monkeypatch.setattr(launch_mod, "_run_static",
                            lambda args: called.setdefault("static", 0) or 0)
        rc = launch_mod.run_commandline(
            ["--gloo", "-np", "1", "cmd"])
        assert rc == 0 and "static" in called


class TestRunController:
    def _fns(self, log):
        return (lambda impl=None: log.append(("mpi", impl)) or 0,
                lambda: log.append("js") or 0,
                lambda: log.append("local") or 0)

    def test_explicit_local_alone_wins(self):
        log = []
        mpi_fn, js_fn, local_fn = self._fns(log)
        rc = launch_mod.run_controller(
            use_mpi=False, mpi_fn=mpi_fn, use_jsrun=False, js_fn=js_fn,
            use_local=True, local_fn=local_fn)
        assert rc == 0 and log == ["local"]

    def test_contradictory_backends_rejected(self):
        """--gloo with --mpi must error, not silently drop one
        (reference horovodrun rejects the combination)."""
        log = []
        mpi_fn, js_fn, local_fn = self._fns(log)
        with pytest.raises(RuntimeError, match="contradictory"):
            launch_mod.run_controller(
                use_mpi=True, mpi_fn=mpi_fn, use_jsrun=False, js_fn=js_fn,
                use_local=True, local_fn=local_fn)
        assert log == []

    def test_explicit_mpi(self, monkeypatch):
        import horovod_tpu.runner.mpi_run as mr
        monkeypatch.setattr(mr, "_default_exec", exec_returning(OMPI_OUT))
        log = []
        mpi_fn, js_fn, local_fn = self._fns(log)
        rc = launch_mod.run_controller(
            use_mpi=True, mpi_fn=mpi_fn, use_jsrun=False, js_fn=js_fn,
            use_local=False, local_fn=local_fn)
        # the controller probes once and hands the detected impl through
        assert rc == 0 and log == [("mpi", OPENMPI_IMPL)]

    def test_jsrun_outside_lsf_errors(self, monkeypatch):
        monkeypatch.delenv("LSB_JOBID", raising=False)
        monkeypatch.delenv("LSB_DJOB_HOSTFILE", raising=False)
        log = []
        mpi_fn, js_fn, local_fn = self._fns(log)
        with pytest.raises(RuntimeError, match="LSF"):
            launch_mod.run_controller(
                use_mpi=False, mpi_fn=mpi_fn, use_jsrun=True, js_fn=js_fn,
                use_local=False, local_fn=local_fn)

    def test_auto_local_hosts_stay_local(self, monkeypatch):
        import horovod_tpu.runner.mpi_run as mr
        monkeypatch.setattr(mr, "_default_exec", exec_returning(OMPI_OUT))
        log = []
        mpi_fn, js_fn, local_fn = self._fns(log)
        args = launch_mod.parse_args(["-np", "2", "cmd"])
        rc = launch_mod.run_controller(
            use_mpi=False, mpi_fn=mpi_fn, use_jsrun=False, js_fn=js_fn,
            use_local=False, local_fn=local_fn, args=args)
        assert rc == 0 and log == ["local"]

    def test_auto_remote_hosts_prefer_mpi(self, monkeypatch):
        import horovod_tpu.runner.mpi_run as mr
        monkeypatch.setattr(mr, "_default_exec", exec_returning(OMPI_OUT))
        monkeypatch.delenv("LSB_JOBID", raising=False)
        monkeypatch.delenv("LSB_DJOB_HOSTFILE", raising=False)
        log = []
        mpi_fn, js_fn, local_fn = self._fns(log)
        args = launch_mod.parse_args(
            ["-np", "2", "-H", "remote1:1,remote2:1", "cmd"])
        rc = launch_mod.run_controller(
            use_mpi=False, mpi_fn=mpi_fn, use_jsrun=False, js_fn=js_fn,
            use_local=False, local_fn=local_fn, args=args)
        assert rc == 0 and log == [("mpi", OPENMPI_IMPL)]

    def test_auto_remote_hosts_no_mpi_fall_back(self, monkeypatch):
        import horovod_tpu.runner.mpi_run as mr
        monkeypatch.setattr(mr, "_default_exec",
                            exec_returning("none", 127))
        monkeypatch.delenv("LSB_JOBID", raising=False)
        monkeypatch.delenv("LSB_DJOB_HOSTFILE", raising=False)
        log = []
        mpi_fn, js_fn, local_fn = self._fns(log)
        args = launch_mod.parse_args(
            ["-np", "2", "-H", "remote1:1,remote2:1", "cmd"])
        rc = launch_mod.run_controller(
            use_mpi=False, mpi_fn=mpi_fn, use_jsrun=False, js_fn=js_fn,
            use_local=False, local_fn=local_fn, args=args)
        assert rc == 0 and log == ["local"]

    def test_cross_identity_not_derived_for_heterogeneous_slurm(self):
        """SLURM per-node lists like '2,4' truncate under parse(); they
        must disqualify the cross derivation, not silently pass the
        uniformity check (round-5 review finding)."""
        from horovod_tpu.config import mpi_task_identity
        env = {"SLURM_PROCID": "5", "SLURM_STEP_NUM_TASKS": "6",
               "SLURM_LOCALID": "1",
               "SLURM_STEP_TASKS_PER_NODE": "2,4"}
        ident = mpi_task_identity(env)
        assert "CROSS_RANK" not in ident and "CROSS_SIZE" not in ident
        # the uniform "N(xM)" form still derives
        env["SLURM_STEP_TASKS_PER_NODE"] = "3(x2)"
        ident = mpi_task_identity(env)
        assert ident["CROSS_RANK"] == 1 and ident["CROSS_SIZE"] == 2


def test_programmatic_run_use_mpi(monkeypatch, tmp_path):
    """run(use_mpi=True) drives workers through the stub mpirun and
    still collects per-rank results through the KV rendezvous
    (reference horovod.run(use_mpi=True))."""
    import stat
    stub_dir = tmp_path / "bin"
    stub_dir.mkdir()
    stub = stub_dir / "mpirun"
    stub.write_text("""#!/usr/bin/env python3
import os, subprocess, sys
args = sys.argv[1:]
if "--version" in args:
    print("mpirun (Open MPI) 4.1.4"); sys.exit(0)
VAL1 = {"-np", "-H", "-x", "--output-filename",
        "-bind-to", "-map-by", "-rank-by"}
VAL0 = {"--allow-run-as-root", "--tag-output"}
np_ = 1; i = 0
while i < len(args):
    a = args[i]
    if a == "-mca":
        i += 3; continue
    if a in VAL1:
        if a == "-np": np_ = int(args[i+1])
        i += 2; continue
    if a in VAL0:
        i += 1; continue
    break
cmd = args[i:]
procs = []
for rank in range(np_):
    env = dict(os.environ)
    env.update({"OMPI_COMM_WORLD_RANK": str(rank),
                "OMPI_COMM_WORLD_SIZE": str(np_),
                "OMPI_COMM_WORLD_LOCAL_RANK": str(rank),
                "OMPI_COMM_WORLD_LOCAL_SIZE": str(np_)})
    procs.append(subprocess.Popen(cmd, env=env))
sys.exit(max(p.wait() for p in procs))
""")
    stub.chmod(stub.stat().st_mode | stat.S_IEXEC)
    monkeypatch.setenv("PATH", f"{stub_dir}{os.pathsep}"
                               f"{os.environ.get('PATH', '')}")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")

    from horovod_tpu.runner.api import run

    def fn(a, b=0):
        import horovod_tpu as hvd
        hvd.init()
        try:
            return a + b + hvd.rank()
        finally:
            hvd.shutdown()

    results = run(fn, args=(10,), kwargs={"b": 5}, np=2, use_mpi=True,
                  disable_ssh_check=True)
    assert results == [15, 16]


def test_mpi_run_strips_driver_scheduler_identity():
    """A driver running inside a SLURM/PMI step must not leak its own
    identity vars into the mpirun process env — locally spawned workers
    would resolve the DRIVER's rank (round-5 review finding)."""
    captured = {}
    mpi_run(basic_settings(),
            {"SLURM_PROCID": "0", "SLURM_STEP_NUM_TASKS": "1",
             "PMI_RANK": "0", "PMI_SIZE": "1",
             "OMPI_COMM_WORLD_RANK": "0", "KEEPME": "1"},
            ["c"], exec_fn=exec_returning(OMPI_OUT),
            spawn_fn=lambda argv, env: captured.update(env=env) or 0)
    env = captured["env"]
    for var in ("SLURM_PROCID", "SLURM_STEP_NUM_TASKS", "PMI_RANK",
                "PMI_SIZE", "OMPI_COMM_WORLD_RANK"):
        assert var not in env, var
    assert env["KEEPME"] == "1"


def test_programmatic_run_use_mpi_reports_aggregate_rc(monkeypatch):
    """ADVICE r5 #4: mpirun yields ONE exit code for the whole gang; a
    failure must be reported as that aggregate code, not synthesized
    into per-rank codes that blame every rank."""
    import horovod_tpu.runner.api as api_mod
    import horovod_tpu.runner.mpi_run as mpi_mod

    monkeypatch.setattr(mpi_mod, "mpi_run",
                        lambda settings, env, command: 137)
    with pytest.raises(RuntimeError) as ei:
        api_mod.run(lambda: None, np=2, use_mpi=True,
                    disable_ssh_check=True)
    msg = str(ei.value)
    assert "mpirun exited with code 137" in msg
    # no fabricated per-rank blame of the whole gang
    assert "workers failed" not in msg
    assert "[(0, 137), (1, 137)]" not in msg


def test_programmatic_run_use_mpi_prefers_per_rank_error(monkeypatch):
    """When a rank DID report an error through the KV rendezvous, that
    specific rank's failure is raised instead of the opaque aggregate
    mpirun code."""
    import pickle

    import horovod_tpu.runner.api as api_mod
    import horovod_tpu.runner.mpi_run as mpi_mod

    def fake_mpi_run(settings, env, command):
        # simulate rank 1 dying after publishing its error payload
        import urllib.request
        port = env["HVD_TPU_RENDEZVOUS_PORT"]
        blob = pickle.dumps({"error": "boom on rank 1"})
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/{api_mod.run_func_result_scope}/1",
            data=blob, method="PUT")
        urllib.request.urlopen(req)
        return 1

    monkeypatch.setattr(mpi_mod, "mpi_run", fake_mpi_run)
    with pytest.raises(RuntimeError, match="rank 1 raised: boom on rank 1"):
        api_mod.run(lambda: None, np=2, use_mpi=True,
                    disable_ssh_check=True)
