"""The benchmark's own tests run on the CPU, at small sizes:

    python -m pytest chipbench/tests
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the copies the tests build are thrown away; so is what they compile
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
