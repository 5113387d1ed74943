"""Pipeline layer: mean host time per step inside
``CkIOPipeline.get_batch_device`` (waiting for the step's read session,
staging the window, dispatching its assembly), in ms."""


def read(r):
    t = r.spans.mean("get_batch_device")
    return None if t is None else 1e3 * t
