"""Read layer: checkpoint bytes over the host time of
``train/checkpoint.restore_tree`` (one CkIO session, one consumer per
leaf), in GB/s."""


def read(r):
    t = r.spans.mean("restore_tree")
    if not t or not r.counters.get("checkpoint_bytes"):
        return None
    return r.counters["checkpoint_bytes"] / t / 1e9
