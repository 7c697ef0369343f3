"""CPU tests of the benchmark harness:

    JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests

Four virtual CPU devices stand in for the four-chip mesh; no test here
needs a chip."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
