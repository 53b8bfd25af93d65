"""The benchmark's own tests: run on the CPU from the checkout root,

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests

They put ``bench`` (the yardstick), ``src`` (the program, which only the
end-to-end tests drive) and the checkout root on the path."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src", BENCH.parent):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
