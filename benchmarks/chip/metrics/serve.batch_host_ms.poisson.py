"""`serve.batch_host_ms` in the open-loop cells, where it moves `p50_ms`:
read as serve.batch_host_ms.py reads it."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _alias import reader  # noqa: E402

read = reader("serve.batch_host_ms")
