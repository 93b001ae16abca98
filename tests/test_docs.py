"""The documents name files that exist. JAX-free.

Every `.py` / `.sh` path that README.md, CLAUDE.md or a file of docs/ writes
in backticks or after `python ` must resolve against the repo root,
`midgpt_tpu/` (the documents write `sampling/serve.py` for
`midgpt_tpu/sampling/serve.py`) or the document's own directory. A document
that sends its reader to a deleted tool fails here, not in the reader's shell.
"""

import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md", "CLAUDE.md"] + sorted(
    os.path.join("docs", f) for f in os.listdir(os.path.join(REPO, "docs")) if f.endswith(".md")
)

_PATH = r"[\w./-]*\w\.(?:py|sh)\b"
_IN_BACKTICKS = re.compile(r"`([^`\n]+)`")
_AFTER_PYTHON = re.compile(r"\bpython3?\s+(" + _PATH + ")")


def named_paths(text):
    """(path, line number) for every code path the text names."""
    found = []
    for lineno, line in enumerate(text.splitlines(), 1):
        spans = _IN_BACKTICKS.findall(line) + _AFTER_PYTHON.findall(line)
        for span in spans:
            if any(mark in span for mark in ("<", "*", "...")):
                continue
            for path in re.findall(r"(?<![\w./-])" + _PATH, span):
                if (path, lineno) not in found:  # `python x.py` in backticks matches twice
                    found.append((path, lineno))
    return found


def resolves(path, doc_dir):
    if path.startswith("/"):
        return True  # the sandbox's own (/root/reference, /opt/...), not this repo's
    path = path.removeprefix("./")
    roots = (REPO, os.path.join(REPO, "midgpt_tpu"), doc_dir)
    return any(os.path.isfile(os.path.join(root, path)) for root in roots)


def test_the_extractor_reads_what_the_documents_write():
    text = "run `python tools/x.py --flag` or `a/b.py:12-14`, `t.py::test_n[p]`;\n    python3 benchmarks/run.py --workload <cell>\n`<name>.py` `configs/*.py` `supervisor_state.json`"
    assert [p for p, _ in named_paths(text)] == ["tools/x.py", "a/b.py", "t.py", "benchmarks/run.py"]


@pytest.mark.parametrize("doc", DOCS)
def test_every_code_path_a_document_names_exists(doc):
    with open(os.path.join(REPO, doc)) as f:
        text = f.read()
    doc_dir = os.path.dirname(os.path.join(REPO, doc))
    missing = sorted({f"{doc}:{n}: {p}" for p, n in named_paths(text) if not resolves(p, doc_dir)})
    assert not missing, "documents name files that do not exist:\n" + "\n".join(missing)
