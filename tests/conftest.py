import os
import sys

# Smoke tests and benches run on the single real CPU device; only the
# dry-run sets xla_force_host_platform_device_count (per its own module).
# The repo root holds `chip_smoke.py`, whose phases the tests call.
ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)
