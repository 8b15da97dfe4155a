"""kernels: the one-token update's share of its roofline in the traced tail:
the least time the chip could take for the bytes it has to move
(roofline_retention.update_bytes: every decode token reads and writes its
row's slot once in every layer; rows from the program's ``ragged.decode_tokens``
at the trace's edges) or for its operations, whichever is longer, over the
traced seconds of the kernel named power_retention_update. Source:
device_trace. Moves tpot_p50_ms."""

from benchmark.layer_metrics import _retention


def read(ctx):
    from benchmark import roofline, roofline_retention as rr
    from benchmark.sut import model_block

    counts = _retention.tail_counts(ctx)
    seconds = _retention.kernel_seconds(ctx, _retention.UPDATE)
    if counts is None or not seconds:
        return None
    model = model_block(ctx["cfg"])
    least = roofline.min_seconds(
        rr.update_flops(model, counts["decode_tokens"]),
        rr.update_bytes(model, counts["decode_tokens"]),
        roofline.peaks_for(ctx["device"]["kind"]))
    return 100.0 * least["seconds"] / seconds
