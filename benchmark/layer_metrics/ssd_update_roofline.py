"""kernels: the one-token SSD update's share of its roofline in the traced
tail: the least time the chip could take for the bytes it has to move
(roofline_ssd.update_bytes: a row that advances one token reads and writes
its state once in every layer; rows x passes from the program's
``ssm.update_rows`` at the trace's edges) or for its operations, whichever
is longer, over the traced seconds of the kernel named mamba2_ssd_update.
Source: device_trace. Moves tpot_p50_ms."""

from benchmark.layer_metrics import _ssd


def read(ctx):
    from benchmark import roofline, roofline_ssd as rs
    from benchmark.sut import model_block

    counts = _ssd.tail_counts(ctx)
    seconds = _ssd.kernel_seconds(ctx, _ssd.UPDATE)
    if counts is None or not seconds:
        return None
    model = model_block(ctx["cfg"])
    least = roofline.min_seconds(
        rs.update_flops(model, counts["update_rows"]),
        rs.update_bytes(model, counts["update_rows"]),
        roofline.peaks_for(ctx["device"]["kind"]))
    return 100.0 * least["seconds"] / seconds
