"""kernels: the SSD chunk kernel's share of its roofline in the traced tail:
the larger of the recurrence's operations over the bf16 peak
(roofline_ssd.chunk_flops: 4 x H x N x P a prompt token and layer) and the
bytes it has to move over the HBM peak (chunk_bytes: a row's state read and
written once a chunk, a token's operands), against the traced seconds of the
kernel named mamba2_ssd_chunk. Rows and tokens from the program's
``ssm.chunk_rows`` / ``chunk_tokens`` at the trace's edges. The kernel runs
in every mixed pass, also where no row brings a chunk, so the share is low
where prompts are rare. Source: device_trace. Moves ttft_p50_ms."""

from benchmark.layer_metrics import _ssd


def read(ctx):
    from benchmark import roofline, roofline_ssd as rs
    from benchmark.sut import model_block

    counts = _ssd.tail_counts(ctx)
    seconds = _ssd.kernel_seconds(ctx, _ssd.CHUNK)
    if counts is None or not seconds:
        return None
    model = model_block(ctx["cfg"])
    least = roofline.min_seconds(
        rs.chunk_flops(model, counts["chunk_tokens"]),
        rs.chunk_bytes(model, counts["chunk_rows"], counts["chunk_tokens"]),
        roofline.peaks_for(ctx["device"]["kind"]))
    return 100.0 * least["seconds"] / seconds
