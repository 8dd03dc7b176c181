"""The user-facing documents name only files that are in the tree."""

import os
import re
import subprocess

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# a path, or a bare file name, with no directory of another tree in front
TOKEN = re.compile(r"(?<![\w./-])((?:[\w.-]+/)*[\w.-]+\.(?:py|json))\b")


def _tracked():
    out = subprocess.run(["git", "ls-files"], cwd=ROOT, capture_output=True, text=True)
    if out.returncode == 0 and out.stdout:
        return [f for f in out.stdout.split() if os.path.exists(os.path.join(ROOT, f))]
    return [os.path.relpath(os.path.join(d, f), ROOT)    # a checkout without git
            for d, _, files in os.walk(ROOT) for f in files]


@pytest.mark.parametrize("document", ["README.md", "docs/api.md", "docs/index.md"])
def test_documents_name_only_files_that_exist(document):
    """Paths under scripts/, benchmarks/ and examples/ exist, round records
    named like SERVE_r05.json exist, and a bare ``name.py`` is some file's
    name. Paths into other trees (the reference's ``srcs/...``) are not
    judged."""
    files = _tracked()
    names = {os.path.basename(f) for f in files}
    with open(os.path.join(ROOT, document)) as f:
        text = f.read()
    missing = set()
    for token in TOKEN.findall(text):
        head, _, base = token.rpartition("/")
        if head.split("/")[0] in ("scripts", "benchmarks", "examples"):
            ok = os.path.exists(os.path.join(ROOT, token))
        elif head:
            continue
        elif re.fullmatch(r"[A-Z]+_r\d+\w*\.json", base):
            ok = os.path.exists(os.path.join(ROOT, base))
        else:
            ok = not base.endswith(".py") or base in names
        if not ok:
            missing.add(token)
    assert not missing, f"{document} names files that are gone: {sorted(missing)}"
