"""Megabytes a request uploads from the host to the devices.

Layer: planner residency (``parallel/stacks.py``: ``StackStore.insert``,
under the span ``stack.upload``). Source: ``upload_bytes`` of
``/debug/device`` (the bytes of every stack upload, summed over the chips
it lands on) over the window, / 1e6, over the answered requests. 0 where
the working set is resident; None on a program whose ``/debug/device``
lacks the key.
"""

KEY = "upload_bytes"


def read(ctx):
    if KEY not in ctx["device1"] or not ctx["answered"]:
        return None
    moved = ctx["device1"][KEY] - ctx["device0"].get(KEY, 0)
    return moved / 1e6 / ctx["answered"]
