"""kernels: device time of the learned selection (the operations under the
step's named scopes ``index_score`` and ``index_topk``: scoring every
visible key of a full layer's rows and the exact top-k) over device-busy
time, in the traced tail. The scopes are read from the trace file
(host_spans.device_ms_by_scope); None where the trace has no operation under
either. Source: device_trace. Moves tpot_p50_ms."""

from benchmark.layer_metrics import _common

SCOPES = ("index_score", "index_topk")


def read(ctx):
    from benchmark import host_spans

    t = _common.traced(ctx)
    if t is None or not t["busy_s"] or not ctx.get("trace_path"):
        return None
    by_scope = host_spans.device_ms_by_scope(ctx["trace_path"], SCOPES)
    ms = sum(v for k, v in by_scope.items() if k in SCOPES)
    if not ms:
        return None
    return 100.0 * ms * 1e-3 / t["devices"] / t["busy_s"]
