"""The documents' commands exist: every `make <target>`, root-level
`python <script>.py`, `python -m mcp_context_forge_tpu.<module>` and
Containerfile `COPY` source that a document names is in the tree. A
script that goes while a document still tells an operator to run it
fails the document's case here."""

import glob
import os
import re

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

DOCUMENTS = ["README.md", "PARITY.md",
             *sorted(os.path.relpath(p, REPO_ROOT) for p in
                     glob.glob(os.path.join(REPO_ROOT, "docs", "*.md"))),
             ".claude/skills/verify/SKILL.md", "docker-entrypoint.sh",
             "Containerfile"]

_MAKE_RE = re.compile(r"\bmake((?:[ \t]+[\w.=$()/-]+)+)")
_SCRIPT_RE = re.compile(r"\bpython3?[ \t]+(?!-)([\w./-]+\.py)\b")
_MODULE_RE = re.compile(r"\bpython3?[ \t]+-m[ \t]+(mcp_context_forge_tpu(?:\.\w+)*)")
_CODE_RE = re.compile(r"```.*?```|`[^`\n]+`", re.DOTALL)


def _make_targets() -> set[str]:
    with open(os.path.join(REPO_ROOT, "Makefile")) as fh:
        return set(re.findall(r"^([A-Za-z0-9_-]+):", fh.read(), re.MULTILINE))


def _commands_text(path: str, text: str) -> str:
    """Where a document can name a command: the code spans and fenced
    blocks of a markdown file (prose says "make sure"), all of a script."""
    if path.endswith(".md"):
        return "\n".join(_CODE_RE.findall(text))
    return text


def _module_exists(dotted: str) -> bool:
    base = os.path.join(REPO_ROOT, *dotted.split("."))
    return os.path.isfile(base + ".py") or os.path.isfile(
        os.path.join(base, "__main__.py"))


@pytest.mark.parametrize("document", DOCUMENTS)
def test_documents_name_only_commands_that_exist(document):
    with open(os.path.join(REPO_ROOT, document)) as fh:
        text = fh.read()
    commands = _commands_text(document, text)
    targets = _make_targets()
    gone = []
    for match in _MAKE_RE.finditer(commands):
        words = [w for w in match.group(1).split()
                 if not w.startswith("-") and "=" not in w]
        # `make a b c` builds three targets in a script; in a document's
        # code span only the first word after `make` is surely a target
        named = words if not document.endswith(".md") else words[:1]
        gone += [f"make {t}" for t in named if t not in targets]
    for script in _SCRIPT_RE.findall(commands):
        # absolute paths are scratch patterns (/tmp/verify_pr14.py)
        if not script.startswith("/") and not os.path.isfile(
                os.path.join(REPO_ROOT, script)):
            gone.append(f"python {script}")
    gone += [f"python -m {m}" for m in _MODULE_RE.findall(commands)
             if not _module_exists(m)]
    if document == "Containerfile":
        for line in text.splitlines():
            words = line.split()
            if words[:1] != ["COPY"] or any(
                    w.startswith("--from=") for w in words):
                continue
            gone += [f"COPY {src}" for src in words[1:-1]
                     if not glob.glob(os.path.join(REPO_ROOT, src))]
    assert not gone, f"{document} names what is not in the tree: {gone}"
