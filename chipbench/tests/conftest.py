import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_ROOT = pathlib.Path(__file__).resolve().parents[2]
for _p in (str(_ROOT / "src"), str(_ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)
