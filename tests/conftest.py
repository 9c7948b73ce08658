"""Test bootstrap: src/ on sys.path and a hermetic autotune cache.

Keeps `python -m pytest` working from the repo root even without an
installed package (pyproject's `pythonpath = ["src"]` does the same for
pytest >= 7; this also covers direct module imports).
"""

import os
import pathlib
import sys
import tempfile

_SRC = str(pathlib.Path(__file__).resolve().parent.parent / "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

# Keep the autotuner's disk cache hermetic: never read/write the real
# <repo>/.cache/repro/autotune.json from the test suite (individual tests
# override this per-case via monkeypatch).
os.environ.setdefault(
    "REPRO_AUTOTUNE_CACHE",
    os.path.join(tempfile.mkdtemp(prefix="repro-autotune-"),
                 "autotune.json"))


def pytest_addoption(parser):
    parser.addoption(
        "--regen-golden", action="store_true", default=False,
        help="rewrite tests/golden/ fixtures from today's outputs "
             "(workload conformance harness) instead of comparing")


import pytest  # noqa: E402


@pytest.fixture
def regen_golden(request) -> bool:
    """True when the run should regenerate golden fixtures in place."""
    return request.config.getoption("--regen-golden")
