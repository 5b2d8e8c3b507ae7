import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the four-chip cell runs its tiny stand-in on four virtual CPU devices
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))
