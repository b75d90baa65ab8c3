"""Host seconds of the sorted-workspace (CSF) build, the harness's clock
around ``Ingested.workspace(plan)`` with its arrays ready on the device."""


def read(ctx):
    return ctx.get("csf_sort_s")
