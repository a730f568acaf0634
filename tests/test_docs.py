"""Docs tree checks: links resolve and the documented API exists
(the reference builds its docs in CI with mocked natives — docs/mocks.py;
here 'build clean' means no dangling links and no phantom symbols)."""

import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = os.path.join(REPO, "docs")

_LINK = re.compile(r"\]\(([^)#]+)(#[^)]*)?\)")

#: every document a reader starts from: the two READMEs and the guides
DOCUMENTS = ["README.md", "examples/README.md"] + sorted(
    "docs/" + f for f in os.listdir(DOCS) if f.endswith(".md"))

#: where a file a document names is looked for, besides the document's
#: own directory for a link: the repo root and the trees documents speak
#: from (`serving/engine.py` is under `horovod_tpu/`, `runners/...` under
#: `perfbench/`)
_ROOTS = ("", "docs", "horovod_tpu", "examples", "tests", "tools",
          "perfbench")
_FILE = (".py", ".json", ".jsonl", ".md", ".cc")
_TICKED = re.compile(r"`([^`\n]+)`")
#: `path.py:12-30`, `path.py::test_name`: the file, less the place in it
_PLACE = re.compile(r"(::[\w\[\]\-.:]+|:\d+(?:-\d+)?(?:, ?\d+(?:-\d+)?)*)$")
#: `<dir>/manifest.json`, `step_*.json`, `{name}.py`, `X=/path/t.json`,
#: `python train.py`: a pattern or a command line, not a file's name
_PLACEHOLDER = re.compile(r"[<>*{}$=\s]|\.\.\.")
#: the reference's tree (upstream Horovod, SURVEY.md): named where a
#: document says what a module here is the counterpart of
_REFERENCE = ("horovod/", "/root/reference/")
#: files a document names that are not the repo's, each with its reason
_NOT_OURS = {
    "train_example.py": "docs/running.md's quickstart: the reader's script",
    "elastic_demo.py": "docs/elastic.md's walk-through: the reader's script",
    "demo_parallel.py": "docs/parallelism.md's walk-through: the reader's "
                        "script",
    "manifest.json": "written into every checkpoint step directory at run "
                     "time (docs/checkpoint.md)",
}


def _named_files(text):
    for m in _TICKED.finditer(text):
        name = _PLACE.sub("", m.group(1).strip())
        if name.endswith(_FILE) and not _PLACEHOLDER.search(name) \
                and not name.startswith(_REFERENCE) \
                and name not in _NOT_OURS:
            yield name


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_file_a_document_names_exists(document):
    """A link's target, and every backticked name that ends like a
    source, data or document file, is a file of this repo: a document
    that cites a benchmark, a record or a module outlives it otherwise
    (ISSUE 29: ten guides cited a results file no run could refresh)."""
    with open(os.path.join(REPO, document)) as f:
        text = f.read()
    here = os.path.dirname(document)
    missing = sorted(
        {m.group(1) for m in _LINK.finditer(text)
         if not m.group(1).startswith(("http://", "https://"))
         and not os.path.exists(os.path.join(REPO, here, m.group(1)))}
        | {name for name in _named_files(text)
           if not any(os.path.exists(os.path.join(REPO, root, name))
                      for root in _ROOTS)})
    assert not missing, (
        f"{document} names files the repo does not have: {missing} "
        f"(a path is looked for from the repo root and from "
        f"{', '.join(r + '/' for r in _ROOTS[1:])})")


def test_docs_exist_and_cover_reference_topics():
    files = {f for f in os.listdir(DOCS) if f.endswith(".md")}
    # the reference's major guide topics (docs/*.rst) must all be covered
    for topic in ["summary", "concepts", "running", "benchmarks",
                  "elastic", "timeline", "autotune", "adasum",
                  "tensor-fusion", "pytorch", "tensorflow", "keras",
                  "mxnet", "spark", "lsf", "troubleshooting", "api",
                  "install", "index", "inference"]:
        assert f"{topic}.md" in files, f"missing docs/{topic}.md"


def test_docs_links_resolve():
    for fname in os.listdir(DOCS):
        if not fname.endswith(".md"):
            continue
        with open(os.path.join(DOCS, fname)) as f:
            text = f.read()
        for m in _LINK.finditer(text):
            target = m.group(1)
            if target.startswith(("http://", "https://")):
                continue
            assert os.path.exists(os.path.join(DOCS, target)), \
                f"{fname}: dangling link {target}"


def test_documented_top_level_api_exists():
    import horovod_tpu as hvd
    for name in ["init", "shutdown", "is_initialized", "rank", "size",
                 "local_rank", "dp_size", "allreduce", "allreduce_async",
                 "grouped_allreduce", "grouped_allreduce_async",
                 "allgather", "broadcast", "grouped_broadcast",
                 "grouped_broadcast_async", "alltoall", "alltoall_async",
                 "poll", "synchronize", "release", "join", "barrier",
                 "DistributedOptimizer", "Average", "Sum", "Adasum",
                 "elastic", "checkpoint", "Estimator"]:
        assert hasattr(hvd, name), f"documented symbol hvd.{name} missing"
    from horovod_tpu import collectives as c
    for name in ["grouped_allreduce_async", "grouped_broadcast",
                 "grouped_broadcast_async", "alltoall_async", "release",
                 "psum", "pmean", "all_gather_in_jit",
                 "reduce_scatter_in_jit"]:
        assert hasattr(c, name), name
    from horovod_tpu import elastic as el
    for name in ["run", "State", "ObjectState", "JaxState",
                 "CommitStateCallback", "UpdateEpochStateCallback"]:
        assert hasattr(el, name), f"hvd.elastic.{name} missing"
    from horovod_tpu import compiled_autotune
    assert hasattr(compiled_autotune, "autotune_variants")
    assert hasattr(compiled_autotune, "tune_distributed_step")


def test_configuration_doc_covers_every_knob():
    """docs/configuration.md is generated from the knob registry; a knob
    added without regenerating the table should fail here, not drift."""
    import os
    from horovod_tpu import config
    path = os.path.join(os.path.dirname(__file__), "..", "docs",
                        "configuration.md")
    with open(path) as f:
        text = f.read()
    for knob in config.knobs().values():
        assert f"HVD_TPU_{knob.name}" in text, (
            f"knob HVD_TPU_{knob.name} missing from docs/configuration.md "
            f"— regenerate the table (see the file header)")
