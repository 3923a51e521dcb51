"""The fused count programs' share of the mesh's HBM roofline, %.

Layer: kernels (``ops/bitops.py``, the fused count program, each chip on
its own shard rows). The bytes are what the requests answered inside the
traced span need (``kernel_bytes.request_bytes``: leaves x real shards x
width / 8, over all chips), the time is the device-seconds busy in that
span: the trace's device planes x their mean busy time. The peak is
``peaks.json``'s for this device kind, each chip's own. None unless the
trace holds one device plane a chip of the configuration. Where the
devices also run stack uploads in that span, they sit in the busy time,
so the share reads lower by construction.
"""

import kernel_bytes


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace.get("busy_s") or not ctx["trace_requests"]:
        return None
    planes = trace.get("device_planes")
    if planes != int(ctx["config"]["chips"]):
        return None
    if ctx["peaks"] is None:
        raise ValueError("no peaks for this device kind")
    need = sum(kernel_bytes.request_bytes(pql, ctx["config"])
               for pql in ctx["trace_requests"])
    share = 100.0 * need / (planes * ctx["peaks"]["hbm_bytes_per_s"]
                            * trace["busy_s"])
    if share > 105.0:
        raise ValueError(
            f"mesh_count_hbm_roofline reads {share:.1f} %: the bytes are "
            f"counted too high or the busy time leaves out part of the work")
    return share
