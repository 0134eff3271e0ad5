"""Names PR 30 removed with the second yardstick and the kernel-adoption
chain stay removed: no document a builder follows, no module, tool, example
or test names them again.  (``CHANGES.md``, ``ROADMAP.md``, ``PERF.md`` and
``SURVEY.md`` keep the history; ``benchmark/`` is the judged harness.)"""

import functools
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
SEARCHED = ("README.md", "DESIGN.md", ".claude/skills/verify/SKILL.md",
            "deeplearning4j_tpu", "tools", "examples", "chip_smoke.py",
            "tests")
SUFFIXES = {".py", ".md", ".json", ".txt", ".cc", ".h", ".toml", ".cfg"}


@functools.cache
def _texts():
    """(path, text) of every searched file, read once for all ten names."""
    out = []
    for entry in SEARCHED:
        root = REPO / entry
        files = [root] if root.is_file() else sorted(
            f for f in root.rglob("*")
            if f.is_file() and f.suffix in SUFFIXES
            and "__pycache__" not in f.parts)
        out += [(f.relative_to(REPO), f.read_text(errors="replace"))
                for f in files if f != Path(__file__).resolve()]
    return out


@pytest.mark.parametrize("name", [
    "bench.py", "tune_tpu", "summarize_tune", "perf_gate", "autopick",
    "flash_attention", "BENCH_TRAJECTORY", "LAST_VALID_TPU_BENCH",
    "BENCH_ATTENTION", "TUNE_r"])
def test_removed_name_occurs_nowhere(name):
    assert (REPO / "README.md").is_file() and (REPO / "tools").is_dir()
    hits = [f"{path}:{n}" for path, text in _texts()
            for n, line in enumerate(text.splitlines(), 1) if name in line]
    assert not hits, f"{name!r} is back: {hits[:10]}"
