"""Share of the answered requests that shared a launch with another, %.

Layer: dispatch. Source: the program's counter
``planner.dispatchCoalesced`` (``/debug/vars``) over the window.
"""


def read(ctx):
    if not ctx["answered"]:
        return None
    delta = ctx["counters1"].get("planner.dispatchCoalesced", 0) \
        - ctx["counters0"].get("planner.dispatchCoalesced", 0)
    return 100.0 * delta / ctx["answered"]
