"""The fused count programs' share of the HBM roofline, %.

Layer: kernels (``ops/bitops.py``, the fused count program). The bytes
are what the requests answered inside the traced span need
(``kernel_bytes.request_bytes``: leaves x real shards x width / 8), the
time is the device's busy time in that span (from the profiler's trace),
the peak is ``peaks.json``'s for this device kind. Bandwidth-bound: a
popcount per word is far under the chip's compute peak. Where the device
also runs stack uploads in that span, they sit in the busy time, so the
share reads lower by construction.
"""

import kernel_bytes


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace.get("busy_s") or not ctx["trace_requests"]:
        return None
    if ctx["peaks"] is None:
        raise ValueError("no peaks for this device kind")
    need = sum(kernel_bytes.request_bytes(pql, ctx["config"])
               for pql in ctx["trace_requests"])
    share = 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / trace["busy_s"]
    if share > 105.0:
        raise ValueError(
            f"count_hbm_roofline reads {share:.1f} %: the bytes are counted "
            f"too high or the busy time leaves out part of the work")
    return share
