"""The benchmark's own tests: CPU only, small shapes, no chip."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
# programs compiled here stay out of the checkout's compile cache
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
