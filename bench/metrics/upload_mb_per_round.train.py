"""Bytes the host loop handed to the device per round of the traced
window, in MB (1e6 B): the change of the ``Trainer.upload_bytes``
counter over the window. None where the trainer has no such counter."""


def read(ctx):
    up = ctx.get("counters", {}).get("upload_bytes")
    if up is None or not ctx["rounds"]:
        return None
    return up / 1e6 / ctx["rounds"]
