"""Share of the devices' busy time spent in collective operations, in
percent: `collective_s` over `busy_s` times the devices that ran
(trace_reduce.py). In the distributed cells that is the stage-2 exchange
of candidates and counts, with each chip's wait there for the slowest
sub-graph walk. Nothing, with a note, where no collective ran in the
traced window."""


def read(ctx):
    t = ctx.trace
    if t is None or t["busy_s"] <= 0:
        return None
    if t["collective_s"] <= 0:
        ctx.note("distributed.collective_share: not read: no collective "
                 "op ran on the devices in the traced window")
        return None
    return 100.0 * t["collective_s"] / (t["busy_s"] * t["devices"])
