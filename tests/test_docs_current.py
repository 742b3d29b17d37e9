"""The documents name what exists: every script, document and record
file they point at is in the tree, and every ``EDL_*`` variable they
describe is read by the program."""

import glob
import io
import os
import re
import tokenize

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# scripts/<name>.py, docs/<name>.md, bench.py, MULTICHIP_*.json; a path
# with more levels (docs/benchmark/report_cn.md) is the reference's
FILE_RE = re.compile(
    r"(?<![\w/.-])((?:scripts|docs)/[\w-]+\.(?:py|md)"
    r"|bench\.py|MULTICHIP_\w+\.json)")
ENV_RE = re.compile(r"EDL_[A-Z0-9_]*[A-Z0-9]")


def _read(path):
    with open(os.path.join(REPO, path), encoding="utf-8") as f:
        return f.read()


def _files(pattern):
    return sorted(
        os.path.relpath(p, REPO)
        for p in glob.glob(os.path.join(REPO, pattern), recursive=True))


def _prose(path):
    """Comments and string literals of a Python file: where prose lives."""
    out = []
    tokens = tokenize.generate_tokens(io.StringIO(_read(path)).readline)
    for token in tokens:
        if token.type in (tokenize.COMMENT, tokenize.STRING):
            out.append(token.string)
    return "\n".join(out)


def test_every_file_the_documents_name_exists():
    texts = {
        path: _read(path)
        for path in ["README.md", "PARITY.md", "scripts/ci.sh",
                     ".github/workflows/ci.yml"]
        + _files("docs/**/*.md")
    }
    for path in _files("elasticdl_tpu/**/*.py"):
        texts[path] = _prose(path)
    missing = sorted(
        "%s names %s" % (path, name)
        for path, text in texts.items()
        for name in set(FILE_RE.findall(text))
        if not os.path.exists(os.path.join(REPO, name))
    )
    assert not missing, "\n".join(missing)


def test_every_knob_the_documents_name_is_read_by_the_program():
    sources = "\n".join(
        _read(path)
        for path in _files("elasticdl_tpu/**/*.py")
        + _files("scripts/*.py") + _files("scripts/*.sh")
        + ["chip_smoke.py"]
    )
    read = set(ENV_RE.findall(sources))
    stale = sorted(
        "%s names %s" % (path, name)
        for path in ["README.md"] + _files("docs/*.md")
        for name in set(ENV_RE.findall(_read(path)))
        # EDL_DEVICE_TIER_* and the like: a family, not a name
        if name not in read
        and not any(r.startswith(name + "_") for r in read)
    )
    assert not stale, "\n".join(stale)
