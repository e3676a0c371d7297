"""Queries per second in the csd cells, read as qps.py reads it: a metric
of its own, so that its bound follows the csd cells' spread."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _alias import reader  # noqa: E402

read = reader("qps")
