"""The benchmark's CPU tests: eight host devices (as the repo's suite
has), and the checkout's root and ``src`` on the path."""
import os
import sys

_FLAGS = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _FLAGS:
    os.environ["XLA_FLAGS"] = (
        _FLAGS + " --xla_force_host_platform_device_count=8").strip()

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(_ROOT, "src"), _ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
