"""The benchmark's tests run from the repository root; they import the
benchmark (``bench``) and the program (``src``) from there."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
