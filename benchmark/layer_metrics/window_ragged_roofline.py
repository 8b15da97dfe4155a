"""kernels: the ragged paged kernel's share of its roofline in the traced
tail, on a model whose layers window: the larger of its least operations
over the bf16 peak (QK^T and PV for every (query, visible key) pair of the
mixed passes, by layer kind: ``window.mixed_pairs_full`` +
``mixed_pairs_window``) and its least bytes over the HBM peak (a row's
visible keys once, the union of its queries' windows on a sliding layer:
``window.mixed_keys_full`` + ``mixed_keys_window``), against the traced
seconds of the operations named ragged_paged_attention. None where the
program has no such counter or the trace lacks the kernel. Source:
device_trace. Moves ttft_p50_ms."""

from benchmark.layer_metrics import _common, _retention, _window

KERNEL = "ragged_paged_attention"


def read(ctx):
    from benchmark import roofline, roofline_window as rw
    from benchmark.sut import model_block

    g = _window.gains(ctx, _common.trace_edges(ctx))
    seconds = _retention.kernel_seconds(ctx, KERNEL)
    if _common.traced(ctx) is None or g is None or not seconds:
        return None
    model = model_block(ctx["cfg"])
    least = roofline.min_seconds(
        rw.attention_flops(
            model, g["mixed_pairs_full"] + g["mixed_pairs_window"]),
        (g["mixed_keys_full"] + g["mixed_keys_window"]) * rw.key_bytes(model),
        roofline.peaks_for(ctx["device"]["kind"]))
    return 100.0 * least["seconds"] / seconds
