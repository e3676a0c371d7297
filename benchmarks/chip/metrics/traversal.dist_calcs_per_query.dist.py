"""`traversal.dist_calcs_per_query` in the distributed cells, where it
moves `qps` over a mesh of chips (the count is summed over the mesh):
read as traversal.dist_calcs_per_query.py reads it."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _alias import reader  # noqa: E402

read = reader("traversal.dist_calcs_per_query")
