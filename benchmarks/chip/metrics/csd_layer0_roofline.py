"""Share of the roofline of csd's hop programs (see _roofline.py). The
counts include the upper layers' distance evaluations, so the time does
too: `_upper_step` beside the layer-0 hop (`_layer0_step`) or superstep
(`_layer0_superstep`), whichever the spec runs."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _roofline import share  # noqa: E402


def read(ctx):
    return share(ctx, "csd_layer0_roofline",
                 ("_layer0_step", "_layer0_superstep", "_upper_step"))
