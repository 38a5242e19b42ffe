"""perfbench's own tests: `pytest perfbench/tests`, CPU only, by hand (tier-1
collects tests/ alone). Four virtual CPU devices for the four-chip mesh."""

import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()

HERE = Path(__file__).resolve().parent
for path in (HERE, HERE.parent, HERE.parent.parent):
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))
