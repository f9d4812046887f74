"""The documents describe the tree that is there: a file they name
exists, and a name the program reads from the environment is documented.
Text only: nothing here imports the program."""

import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]

# named in a document or a comment and rightly not in the repo
NOT_IN_THE_REPO = {
    "chaos_verdict.json": "written by `chaos run` into its report dir",
    "cost_profiles.json": "written into an --observe run dir",
    "metrics_cluster.json": "written into an --observe run dir",
    "config.json": "a model's published description, by its usual name",
    "bin/pipelines-ec2.sh": "the reference's launcher",
}

_INLINE_CODE = re.compile(r"`([^`\n]+)`")
_FILE_NAME = re.compile(r"[\w./-]+\.(?:py|json|md|sh)\b(?!l)")


def _program_files():
    return sorted((ROOT / "keystone_tpu").rglob("*.py"))


def _exists(name: str, basenames: set[str]) -> bool:
    if name in NOT_IN_THE_REPO:
        return True
    if (ROOT / name).exists() or (ROOT / "keystone_tpu" / name).exists():
        return True
    # `queue.py`, `run.py`: a bare file name, wherever it lives
    return "/" not in name and name in basenames


@pytest.mark.parametrize("doc", ["README.md", "PARITY.md"])
def test_every_file_a_document_names_exists(doc):
    """Paths in backticks are relative to the repo's root or to
    ``keystone_tpu/``; a bare file name may live anywhere in the tree."""
    basenames = {
        p.name
        for p in ROOT.rglob("*")
        # not .git, nor the unpacked trees and outputs of a chip run
        if p.is_file()
        and not any(part.startswith(".") for part in p.relative_to(ROOT).parts)
        and "chiprun_out" not in p.parts
    }
    named = {
        m.group(0)
        for span in _INLINE_CODE.findall((ROOT / doc).read_text())
        for m in _FILE_NAME.finditer(span)
    }
    assert named, "the pattern went stale"
    missing = sorted(n for n in named if not _exists(n, basenames))
    assert not missing, f"{doc} names files that are not in the tree: {missing}"


def test_no_program_comment_cites_a_file_that_is_gone():
    """A record at the root (``PERF.md``, ``BASELINE.md``) or a path
    under tools/, bin/, benchmarks/, tests/ or native/ that a comment or
    docstring of the program names has to be there to be read."""
    at_root = re.compile(r"(?<![\w/.])[A-Z][A-Z0-9_]{2,}\.(?:md|json)\b")
    by_path = re.compile(
        r"(?<![\w/.])(?:tools|bin|benchmarks|tests|native)/[\w/.-]+"
        r"\.(?:py|sh|json|cpp|md)\b"
    )
    gone: dict[str, list[str]] = {}
    cited = 0
    for path in _program_files():
        text = path.read_text()
        for pat in (at_root, by_path):
            for name in pat.findall(text):
                cited += 1
                if name not in NOT_IN_THE_REPO and not (ROOT / name).exists():
                    gone.setdefault(name, []).append(str(path.relative_to(ROOT)))
    assert cited, "the patterns went stale"
    assert not gone, f"cited under keystone_tpu/ and not in the tree: {gone}"


def test_every_environment_name_the_program_reads_is_in_the_readme():
    pat = re.compile(r"\b(?:KST|KEYSTONE)_[A-Z0-9_]*[A-Z0-9]\b")
    names = {n for path in _program_files() for n in pat.findall(path.read_text())}
    assert names, "the pattern went stale"
    readme = (ROOT / "README.md").read_text()
    missing = sorted(n for n in names if not re.search(rf"\b{n}\b", readme))
    assert not missing, f"read under keystone_tpu/, not in README.md: {missing}"
