"""Row stacks built on the host and uploaded, per answered request.

Layer: planner residency (``parallel/planner.py``, ``exec/residency.py``,
``parallel/prefetch.py``). Source: ``uploads`` of ``/debug/device`` over
the window. 0 where the working set is resident.
"""


def read(ctx):
    if not ctx["answered"]:
        return None
    return (ctx["device1"]["uploads"] - ctx["device0"]["uploads"]) \
        / ctx["answered"]
