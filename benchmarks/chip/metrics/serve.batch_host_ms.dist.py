"""`serve.batch_host_ms` in the distributed cells, where it moves `qps`
over a mesh of chips: read as serve.batch_host_ms.py reads it."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _alias import reader  # noqa: E402

read = reader("serve.batch_host_ms")
