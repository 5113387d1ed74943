"""Stage layer: checkpoint bytes over the host time from the first
``device_put`` of a restored leaf until every leaf is resident, in GB/s."""


def read(r):
    t = r.spans.mean("device_put")
    if not t or not r.counters.get("checkpoint_bytes"):
        return None
    return r.counters["checkpoint_bytes"] / t / 1e9
