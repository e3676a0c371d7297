"""`device.idle_share` in the distributed cells, where it moves `qps`
over a mesh of chips (busy time is the mean over the chips): read as
device.idle_share.py reads it."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _alias import reader  # noqa: E402

read = reader("device.idle_share")
