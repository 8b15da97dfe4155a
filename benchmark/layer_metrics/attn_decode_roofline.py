"""kernels: the paged decode kernel's share of its roofline in the traced
tail: the bytes the algorithm must read (every token a row attends in a
chained decode pass, its K and V in every layer, once:
``ragged.decode_chain_kv_tokens`` gained between the trace's edges x
roofline.kv_bytes_per_token) over the HBM peak, against the traced seconds of
the operations named paged_attention_decode. None where the program has no
such counter or no paged pool, or the trace lacks the kernel although rows
were chained; 0 where no row was chained in the tail. Source: device_trace.
Moves tpot_p50_ms."""

from benchmark.layer_metrics import _common, _retention

KERNEL = "paged_attention_decode"


def read(ctx):
    from benchmark import roofline
    from benchmark.sut import model_block

    edges = _common.trace_edges(ctx)
    if (_common.traced(ctx) is None or edges is None
            or _common.dig(edges[1], "kv_pool") is None):
        return None
    tokens = _common.delta(ctx, "ragged", "decode_chain_kv_tokens", edges=edges)
    seconds = _retention.kernel_seconds(ctx, KERNEL)
    if tokens is None or (tokens and not seconds):
        return None
    if not tokens:
        return 0.0
    peaks = roofline.peaks_for(ctx["device"]["kind"])
    nbytes = tokens * roofline.kv_bytes_per_token(model_block(ctx["cfg"]))
    return 100.0 * nbytes / peaks["hbm_bytes_per_s"] / seconds
