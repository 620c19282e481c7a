"""Put the checkout root (for ``chipbench``) and ``src`` (the program) on
the path, and keep these CPU tests off any accelerator."""
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
