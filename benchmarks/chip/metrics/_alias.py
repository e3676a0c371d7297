"""A metric read the same way as another, under a name of its own: one
quantity split by the end-to-end metric that its cells report (`qps` in
`hbm.saturate`, `qps.csd` in `csd.saturate`)."""

import importlib.util
import os


def reader(name: str):
    """The `read` of metrics/<name>.py."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_alias_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
