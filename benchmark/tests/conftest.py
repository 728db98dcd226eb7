"""CPU only, with four virtual devices for the sharded path, and no
compile cache on disk."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
sys.path.insert(0, ROOT)
