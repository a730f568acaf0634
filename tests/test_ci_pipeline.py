"""CI pipeline generation tests (reference:
/root/reference/test/test_buildkite.py validates gen-pipeline.sh output
against the compose matrix)."""

import os
import re
import subprocess
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "ci"))

from gen_pipeline import (  # noqa: E402
    COMMON_SUITES, EXTRA_SUITES, build_pipeline, emit_yaml,
    parse_compose_services)


def test_compose_services_parsed():
    svcs = parse_compose_services()
    assert "test-cpu-base" not in svcs
    assert "test-cpu-jax_only-py3_12" in svcs
    assert "test-cpu-openmpi-py3_12" in svcs
    assert "test-cpu-mpich-py3_12" in svcs
    assert "test-cpu-mxnet-py3_11" in svcs
    assert len(svcs) >= 6


def test_every_service_gets_build_and_suites():
    svcs = parse_compose_services()
    steps = build_pipeline(svcs)
    builds = {s["key"] for s in steps if "key" in s}
    assert builds == {f"build-{s}" for s in svcs}
    # every service runs every common suite, after its build
    for svc in svcs:
        mine = [s for s in steps if s.get("depends_on") == f"build-{svc}"]
        labels = {s["label"] for s in mine}
        for name, _cmd, _t in COMMON_SUITES:
            assert any(name in l for l in labels), (svc, labels)
    # launcher/bridge extras land exactly on the matching services
    for needle, extras in EXTRA_SUITES.items():
        for svc in svcs:
            mine = [s["label"] for s in steps
                    if s.get("depends_on") == f"build-{svc}"]
            for name, _cmd, _t in extras:
                if needle in svc:
                    assert any(name in l for l in mine), (svc, mine)
                else:
                    assert not any(name in l for l in mine), (svc, mine)


def test_wait_barrier_between_build_and_test():
    steps = build_pipeline(parse_compose_services())
    kinds = ["wait" if list(s.keys()) == ["wait"] else
             ("build" if "key" in s else "test") for s in steps]
    w = kinds.index("wait")
    assert all(k == "build" for k in kinds[:w])
    assert all(k == "test" for k in kinds[w + 1:])


def test_step_commands_reference_existing_paths():
    """Every pytest path named in a generated command must exist — a
    renamed test file must fail generation review, not a nightly."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    steps = build_pipeline(parse_compose_services())
    for s in steps:
        for path in re.findall(r"tests/[A-Za-z0-9_/.]+", s.get("command", "")):
            assert os.path.exists(os.path.join(root, path)), \
                (path, s["command"])
    assert os.path.exists(os.path.join(root, "ci/docker-compose.test.yml"))


def test_emitted_yaml_shape():
    out = emit_yaml(build_pipeline(parse_compose_services()))
    assert out.startswith("steps:")
    assert "- wait" in out
    # quick structural sanity: every step line pair label->command
    labels = out.count("- label:")
    commands = out.count("  command:")
    assert labels == commands and labels > 10


def test_cli_runs():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, os.path.join(root, "ci", "gen_pipeline.py")],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0
    assert r.stdout.startswith("steps:")


# ---------------------------------------------------------------------------
# robustness satellites: knob lint + chaos subset are first-class CI suites
# ---------------------------------------------------------------------------

def test_lint_and_chaos_suites_in_every_service():
    names = [name for name, _cmd, _t in COMMON_SUITES]
    assert "lint-knobs" in names
    assert "chaos" in names
    by_name = {name: cmd for name, cmd, _t in COMMON_SUITES}
    assert by_name["lint-knobs"] == "python tools/check_knobs.py"
    assert "-m chaos" in by_name["chaos"]
    # and the tool the lint step invokes actually exists
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert os.path.exists(os.path.join(root, "tools", "check_knobs.py"))


def test_chaos_coordinator_suite_is_seeded_and_exclusive():
    """The coordinator-kill + heartbeat-timeout drills run as their own
    CI suite with a pinned HVD_TPU_FAULT_SEED (deterministic replay), and
    the generic chaos suite must not run the same file twice."""
    by_name = {name: cmd for name, cmd, _t in COMMON_SUITES}
    assert "chaos-coordinator" in by_name
    cmd = by_name["chaos-coordinator"]
    assert "HVD_TPU_FAULT_SEED=" in cmd
    assert "tests/test_coordinator_recovery.py" in cmd
    assert "--ignore=tests/test_coordinator_recovery.py" in by_name["chaos"]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert os.path.exists(
        os.path.join(root, "tests", "test_coordinator_recovery.py"))


def test_chaos_preempt_suite_is_seeded_and_exclusive():
    """The preemption drills (preempt fault kind, graceful drain,
    scale-policy knobs, drain-vs-checkpoint races, 2-proc e2e drill)
    run as their own seeded CI suite; the generic unit and chaos suites
    must not run the same file twice."""
    by_name = {name: cmd for name, cmd, _t in COMMON_SUITES}
    assert "chaos-preempt" in by_name
    cmd = by_name["chaos-preempt"]
    assert "HVD_TPU_FAULT_SEED=" in cmd
    assert "tests/test_preemption.py" in cmd
    assert "--ignore=tests/test_preemption.py" in by_name["unit"]
    assert "--ignore=tests/test_preemption.py" in by_name["chaos"]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert os.path.exists(
        os.path.join(root, "tests", "test_preemption.py"))


def test_checkpoint_suite_is_seeded_and_exclusive():
    """The checkpointing drills (writer crash, corruption walk-back, GC)
    run as their own seeded CI suite; the generic unit and chaos suites
    must not run the same file twice."""
    by_name = {name: cmd for name, cmd, _t in COMMON_SUITES}
    assert "checkpoint" in by_name
    cmd = by_name["checkpoint"]
    assert "HVD_TPU_FAULT_SEED=" in cmd
    assert "tests/test_checkpointing.py" in cmd
    assert "--ignore=tests/test_checkpointing.py" in by_name["unit"]
    assert "--ignore=tests/test_checkpointing.py" in by_name["chaos"]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert os.path.exists(
        os.path.join(root, "tests", "test_checkpointing.py"))


def test_serving_suite_is_seeded_and_exclusive():
    """The inference-serving suite (micro-batching, admission control,
    hot-reload, forward/reload chaos drills) runs seeded as its own CI
    suite; the generic unit and chaos suites must not run the file
    twice."""
    by_name = {name: cmd for name, cmd, _t in COMMON_SUITES}
    assert "serving" in by_name
    cmd = by_name["serving"]
    assert "HVD_TPU_FAULT_SEED=" in cmd
    assert "tests/test_serving.py" in cmd
    assert "--ignore=tests/test_serving.py" in by_name["unit"]
    assert "--ignore=tests/test_serving.py" in by_name["chaos"]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert os.path.exists(os.path.join(root, "tests", "test_serving.py"))


def test_fleet_suite_is_seeded_and_exclusive():
    """The serving-fleet suite (router health/balancing, per-tenant
    fair admission, rolling hot-reload, and the fleet.route /
    fleet.drain / fleet.health chaos drills) runs seeded as its own CI
    suite; the generic unit and chaos suites must not run the file
    twice, and the single-replica serving suite stays scoped to its
    own file."""
    by_name = {name: cmd for name, cmd, _t in COMMON_SUITES}
    assert "serving-fleet" in by_name
    cmd = by_name["serving-fleet"]
    assert "HVD_TPU_FAULT_SEED=" in cmd
    assert "tests/test_fleet.py" in cmd
    assert "--ignore=tests/test_fleet.py" in by_name["unit"]
    assert "--ignore=tests/test_fleet.py" in by_name["chaos"]
    assert "tests/test_fleet.py" not in by_name["serving"]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert os.path.exists(os.path.join(root, "tests", "test_fleet.py"))


def test_fleet_failover_suite_is_seeded_and_exclusive():
    """The request-survivability suite (end-to-end deadline stages,
    EDF-within-tenant, hedged retries under retry budgets, and the
    mid-stream fleet.stream failover drill with its bit-identity
    proof) runs seeded as its own CI suite; the generic unit and chaos
    suites must not run the file twice."""
    by_name = {name: cmd for name, cmd, _t in COMMON_SUITES}
    assert "chaos-fleet-failover" in by_name
    cmd = by_name["chaos-fleet-failover"]
    assert "HVD_TPU_FAULT_SEED=" in cmd
    assert "tests/test_failover.py" in cmd
    assert "--ignore=tests/test_failover.py" in by_name["unit"]
    assert "--ignore=tests/test_failover.py" in by_name["chaos"]
    assert "tests/test_failover.py" not in by_name["serving-fleet"]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert os.path.exists(os.path.join(root, "tests", "test_failover.py"))


def test_generation_suite_is_seeded_and_exclusive():
    """The continuous-batching generation suite (paged KV cache,
    decode parity, preemption, prefill/decode/evict chaos drills, the
    device-resident sampling/async loop tests, and the prefix-cache
    suite) runs seeded as its own CI suite; the generic unit and chaos
    suites must not run the files twice, and the serving suite stays
    scoped to its own file."""
    by_name = {name: cmd for name, cmd, _t in COMMON_SUITES}
    assert "serving-gen" in by_name
    cmd = by_name["serving-gen"]
    assert "HVD_TPU_FAULT_SEED=" in cmd
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for fname in ("tests/test_generation.py",
                  "tests/test_generation_sampling.py",
                  "tests/test_generation_prefix.py"):
        assert fname in cmd
        assert f"--ignore={fname}" in by_name["unit"]
        assert f"--ignore={fname}" in by_name["chaos"]
        assert fname not in by_name["serving"]
        assert os.path.exists(os.path.join(root, *fname.split("/")))


def test_disagg_suite_is_seeded_and_exclusive():
    """The disaggregated-serving suite (KV-block wire codec, allocator
    export/import round trips, pool-split fleet bit-parity, zero-byte
    warm transfers, the transfer deadline stage, and the seeded
    disagg.transfer mid-transfer kill drill) runs seeded as its own CI
    suite; the generic unit and chaos suites must not run the file
    twice, and the colocated fleet suites stay scoped to their own
    files."""
    by_name = {name: cmd for name, cmd, _t in COMMON_SUITES}
    assert "serving-disagg" in by_name
    cmd = by_name["serving-disagg"]
    assert "HVD_TPU_FAULT_SEED=" in cmd
    assert "tests/test_disagg.py" in cmd
    assert "--ignore=tests/test_disagg.py" in by_name["unit"]
    assert "--ignore=tests/test_disagg.py" in by_name["chaos"]
    assert "tests/test_disagg.py" not in by_name["serving-fleet"]
    assert "tests/test_disagg.py" not in by_name["chaos-fleet-failover"]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert os.path.exists(os.path.join(root, "tests", "test_disagg.py"))


def test_spec_suite_is_seeded_and_exclusive():
    """The speculative-decoding + beam-search suite (n-gram drafting
    with batched verification bit-identical to plain decode, the
    failover-during-spec-decode drill, the seeded serving.verify chaos
    drill, beam-vs-oracle parity, and the capability health surfaces)
    runs seeded as its own CI suite; the generic unit and chaos suites
    must not run the file twice, and the neighboring generation suites
    stay scoped to their own files."""
    by_name = {name: cmd for name, cmd, _t in COMMON_SUITES}
    assert "serving-spec" in by_name
    cmd = by_name["serving-spec"]
    assert "HVD_TPU_FAULT_SEED=" in cmd
    assert "tests/test_speculative.py" in cmd
    assert "--ignore=tests/test_speculative.py" in by_name["unit"]
    assert "--ignore=tests/test_speculative.py" in by_name["chaos"]
    assert "tests/test_speculative.py" not in by_name["serving-gen"]
    assert "tests/test_speculative.py" not in by_name["chaos-fleet-failover"]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert os.path.exists(os.path.join(root, "tests",
                                       "test_speculative.py"))


def test_chaos_sdc_suite_is_seeded_and_exclusive():
    """The silent-data-corruption drills (step guard, fingerprints,
    skip/rollback/quarantine policy, 2-proc bitflip e2e drill) run as
    their own seeded CI suite; the generic unit and chaos suites must
    not run the same file twice."""
    by_name = {name: cmd for name, cmd, _t in COMMON_SUITES}
    assert "chaos-sdc" in by_name
    cmd = by_name["chaos-sdc"]
    assert "HVD_TPU_FAULT_SEED=" in cmd
    assert "tests/test_sdc.py" in cmd
    assert "--ignore=tests/test_sdc.py" in by_name["unit"]
    assert "--ignore=tests/test_sdc.py" in by_name["chaos"]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert os.path.exists(os.path.join(root, "tests", "test_sdc.py"))


def test_chaos_mesh_suite_is_seeded_and_exclusive():
    """The mesh-aware elastic recovery drills (reshape-policy units,
    replica-group-scoped fingerprints, driver mesh plane, shard-handoff
    restore, the seeded 2-proc worker.mesh kill drill) run as their own
    seeded CI suite; the generic unit and chaos suites must not run the
    same file twice."""
    by_name = {name: cmd for name, cmd, _t in COMMON_SUITES}
    assert "chaos-mesh" in by_name
    cmd = by_name["chaos-mesh"]
    assert "HVD_TPU_FAULT_SEED=" in cmd
    assert "tests/test_mesh_elastic.py" in cmd
    assert "--ignore=tests/test_mesh_elastic.py" in by_name["unit"]
    assert "--ignore=tests/test_mesh_elastic.py" in by_name["chaos"]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert os.path.exists(os.path.join(root, "tests",
                                       "test_mesh_elastic.py"))


def test_observability_suite_is_seeded_and_exclusive():
    """The per-request tracing suite (span propagation units, the
    zero-overhead contract, the tools.trace merger, the seeded 2-proc
    router->replica->collective drill) runs as its own seeded CI suite;
    the generic unit and chaos suites must not run the same file
    twice."""
    by_name = {name: cmd for name, cmd, _t in COMMON_SUITES}
    assert "observability" in by_name
    cmd = by_name["observability"]
    assert "HVD_TPU_FAULT_SEED=" in cmd
    assert "tests/test_tracing.py" in cmd
    assert "--ignore=tests/test_tracing.py" in by_name["unit"]
    assert "--ignore=tests/test_tracing.py" in by_name["chaos"]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert os.path.exists(os.path.join(root, "tests", "test_tracing.py"))
    assert os.path.exists(os.path.join(root, "tools", "trace.py"))


def test_lint_static_suite_in_every_service():
    """The unified static-analysis suite (tools/analyze: lock-discipline,
    lock-order, contract lints, jit-purity, knobs, plus the
    distributed-semantics passes collective-divergence /
    collective-contract / mesh-axis) runs as its own CI suite on every
    service, and the module it invokes registers all nine checkers."""
    names = [name for name, _cmd, _t in COMMON_SUITES]
    assert "lint-static" in names
    by_name = {name: cmd for name, cmd, _t in COMMON_SUITES}
    assert by_name["lint-static"] == "python -m tools.analyze"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert os.path.exists(os.path.join(root, "tools", "analyze",
                                       "__main__.py"))
    import sys
    if root not in sys.path:
        sys.path.insert(0, root)
    from tools.analyze import ALL_CHECKERS, CHECKERS  # noqa: F401
    assert len(CHECKERS) == 9, sorted(CHECKERS)
    for name in ("collective-divergence", "collective-contract",
                 "mesh-axis"):
        assert name in CHECKERS
    # the "tree is lint-clean" contract itself is asserted once, in
    # tests/test_static_analysis.py (in-process + CLI) — not repeated
    # here: tier-1 is wallclock-budgeted and each full-repo analysis
    # run costs seconds


def test_check_knobs_lint_is_clean():
    """The knob lint must pass on the tree as committed: every HVD_TPU_*
    env var read in the package is registered in config.py and documented
    in docs/configuration.md."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "check_knobs.py")],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr


def test_check_knobs_detects_unregistered_read(tmp_path, monkeypatch):
    """Seed a stray env read into a scanned copy of the package and the
    lint must flag it (the tool tests its own teeth)."""
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools"))
    import check_knobs
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "rogue.py").write_text(
        'import os\nX = os.environ.get("HVD_TPU_TOTALLY_UNREGISTERED")\n')
    refs = check_knobs.referenced_vars(str(pkg))
    assert "HVD_TPU_TOTALLY_UNREGISTERED" in refs
    assert "HVD_TPU_TOTALLY_UNREGISTERED" not in check_knobs.registered_vars()
