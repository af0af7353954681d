import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# as bench/run.py sets it, before JAX is imported
os.environ.setdefault("JAX_USE_SIMPLIFIED_JAXPR_CONSTANTS", "1")
