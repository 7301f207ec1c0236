import os
import sys

# tests run on the single CPU device (the dry-run sets its own XLA_FLAGS
# in-process and is exercised via subprocess in test_dryrun.py). The
# checkout root is on the path for chip_smoke.py.
_ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "src"))


def pytest_addoption(parser):
    parser.addoption(
        "--update-goldens", action="store_true", default=False,
        help="rewrite tests/goldens/*.json from the current code instead "
             "of comparing against them (commit the diff intentionally)")
