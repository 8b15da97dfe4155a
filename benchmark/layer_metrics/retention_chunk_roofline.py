"""kernels: the chunk kernel's share of its roofline in the traced tail: the
least time the chip could take for its operations over the bf16 peak
(roofline_retention.chunk_flops over the prompt tokens that rode as chunk
rows) or for its state bytes where those bind (chunk_bytes: a chunk reads and
writes its row's slot once a layer), over the traced seconds of the kernel
named power_retention_chunk. Tokens and rows from the program's
``ragged.prefill_tokens`` and ``ragged.step_rows.prefill`` at the trace's
edges. Source: device_trace. Moves tpot_p50_ms."""

from benchmark.layer_metrics import _retention


def read(ctx):
    from benchmark import roofline, roofline_retention as rr
    from benchmark.sut import model_block

    counts = _retention.tail_counts(ctx)
    seconds = _retention.kernel_seconds(ctx, _retention.CHUNK)
    if counts is None or not seconds:
        return None
    model = model_block(ctx["cfg"])
    least = roofline.min_seconds(
        rr.chunk_flops(model, counts["prefill_tokens"], counts["chunk_rows"]),
        rr.chunk_bytes(model, counts["chunk_rows"]),
        roofline.peaks_for(ctx["device"]["kind"]))
    return 100.0 * least["seconds"] / seconds
