"""Share of the roofline of `search_partitioned`, the one program that
runs a whole in-HBM batch (see _roofline.py)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _roofline import share  # noqa: E402


def read(ctx):
    return share(ctx, "search_partitioned_roofline", ("search_partitioned",))
