"""``knobs``: the env-knob registry contract, folded in from
``tools/check_knobs.py`` (which remains as a thin shim for the
``lint-knobs`` CI suite and existing docs).

Every ``HVD_TPU_*`` environment variable referenced anywhere in the
``horovod_tpu`` package must be registered in the knob registry
(``horovod_tpu/config.py``) and documented in
``docs/configuration.md``, and every registered knob must be
documented. A knob read with a bare ``os.environ.get(...)`` silently
escapes CLI flags, YAML config, provenance reporting and the docs
table; this lint turns that drift into a CI failure.
"""

import os
import re
import sys
from typing import Dict, List

from .core import REPO, Context, Finding, checker

#: internal contract / bootstrap vars: read by the package but not user
#: knobs, each with the reason it is exempt from registration
ALLOWLIST = {
    # launcher->worker elastic contract (computed per job, never user-set
    # as a tuning knob; ELASTIC_STATE_DIR is honored if pre-set but its
    # lifecycle is owned by the launcher)
    "HVD_TPU_RESTART_STATE_FILE": "re-exec handoff file, set by reset()",
    "HVD_TPU_ELASTIC_STATE_DIR": "durable-commit dir, launcher-managed",
    "HVD_TPU_ELASTIC_JOB_ID": "job-unique token, launcher-generated",
    # pre-registry bootstrap: resolved before/without any Config instance
    "HVD_TPU_NATIVE": "gates the native build before config can load",
    "HVD_TPU_JOB_SEED": "mpirun wrapper job token, launcher-internal",
}

#: prefix families exempt wholesale (self-contained harness contracts)
ALLOW_PREFIXES = (
    "HVD_TPU_FAULT_SPEC_",  # (reserved)
)

_VAR = re.compile(r"HVD_TPU_[A-Z0-9_]+")


def referenced_vars(root: str = None,
                    repo_root: str = None) -> Dict[str, List[str]]:
    """{var: [file:line, ...]} for every HVD_TPU_* literal in the package
    (config.py excluded — it composes names from the registry). ``root``
    is the package directory (the check_knobs.py shim's historical
    interface); defaults to ``<repo_root>/horovod_tpu``."""
    repo_root = repo_root or REPO
    root = root or os.path.join(repo_root, "horovod_tpu")
    refs: Dict[str, List[str]] = {}
    for dirpath, _dirs, files in os.walk(root):
        for fname in sorted(files):
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            if os.path.relpath(path, root) == "config.py":
                continue
            with open(path, encoding="utf-8") as f:
                for lineno, line in enumerate(f, 1):
                    for m in _VAR.finditer(line):
                        refs.setdefault(m.group(0), []).append(
                            f"{os.path.relpath(path, repo_root)}:{lineno}")
    return refs


def registered_vars(repo_root: str = None):
    repo_root = repo_root or REPO
    if os.path.abspath(repo_root) == os.path.abspath(REPO):
        # the real repo: import the live registry (authoritative — it
        # also catches registration-time errors)
        if REPO not in sys.path:
            sys.path.insert(0, REPO)
        from horovod_tpu import config
        return {"HVD_TPU_" + k for k in config.knobs()}
    # alternate root (fixture repos, external checkouts): parse the
    # _register(...) literals statically instead of importing foreign code
    cfg = os.path.join(repo_root, "horovod_tpu", "config.py")
    if not os.path.exists(cfg):
        return set()
    with open(cfg, encoding="utf-8") as f:
        return {"HVD_TPU_" + name for name in
                re.findall(r'_register\(\s*["\']([A-Z0-9_]+)["\']',
                           f.read())}


def documented_vars(path: str = None, repo_root: str = None):
    path = path or os.path.join(repo_root or REPO,
                                "docs", "configuration.md")
    if not os.path.exists(path):
        return set()
    with open(path, encoding="utf-8") as f:
        return set(_VAR.findall(f.read()))


def check() -> List[str]:
    """Violation strings (empty = clean) — the check_knobs.py shim's
    historical interface."""
    return [f.message for f in _findings(REPO)]


def _findings(repo_root: str) -> List[Finding]:
    refs = referenced_vars(repo_root=repo_root)
    registered = registered_vars(repo_root)
    documented = documented_vars(repo_root=repo_root)
    out: List[Finding] = []
    for var in sorted(refs):
        if var in ALLOWLIST or var.startswith(ALLOW_PREFIXES):
            continue
        if var not in registered:
            where = refs[var][0]
            path, _, line = where.partition(":")
            out.append(Finding(
                "knobs", path, int(line or 1),
                f"{var}: referenced ({', '.join(refs[var][:3])}) but not "
                f"registered in horovod_tpu/config.py — register it or "
                f"allowlist it in tools/analyze/knobs.py with a reason"))
    for var in sorted(registered - documented):
        out.append(Finding(
            "knobs", "horovod_tpu/config.py", 1,
            f"{var}: registered in config.py but missing from "
            f"docs/configuration.md — add a table row"))
    return out


@checker("knobs")
def run(ctx: Context) -> List[Finding]:
    return _findings(ctx.root)
