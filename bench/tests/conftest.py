"""Makes the benchmark's harness and the program importable for the
benchmark's own tests (run on the CPU; nothing here needs a chip)."""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT / "bench", ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
