"""The benchmark's own tests run on the CPU, at tiny sizes."""
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
for p in (os.path.join(CHECKOUT, "src"), HERE):
    if p not in sys.path:
        sys.path.insert(0, p)
