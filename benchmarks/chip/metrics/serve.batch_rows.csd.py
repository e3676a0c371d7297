"""`serve.batch_rows` in the csd cells, where it moves `qps.csd`: read as
serve.batch_rows.py reads it."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _alias import reader  # noqa: E402

read = reader("serve.batch_rows")
