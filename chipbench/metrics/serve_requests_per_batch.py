"""Requests completed in the window over the batches the server's queue
executed in it (``BatchQueue.batches_executed``)."""


def read(ctx):
    if ctx["kind"] != "open_loop" or not ctx["batches"]:
        return None
    return ctx["completed"] / ctx["batches"]
