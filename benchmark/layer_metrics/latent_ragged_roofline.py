"""kernels: the latent ragged kernel's share of its roofline in the traced
tail: the larger of its least operations over the bf16 peak (the expanded
form's count for every (query, visible key) pair of the mixed passes:
``latent.index_keys_kept`` and ``window_keys`` less the chained passes'
``decode_keys_*``) and its least bytes over the HBM peak (a row's selected
or windowed latent rows once: ``latent.mixed_keys_*``), against the traced
seconds of the operations named latent_ragged_attention. None where the
program has no such counter or the trace lacks the kernel. Source:
device_trace. Moves ttft_p50_ms."""

from benchmark.layer_metrics import _common, _latent, _retention

KERNEL = "latent_ragged_attention"


def read(ctx):
    from benchmark import roofline, roofline_latent as rl
    from benchmark.sut import model_block

    g = _latent.gains(ctx, _common.trace_edges(ctx))
    seconds = _retention.kernel_seconds(ctx, KERNEL)
    if _common.traced(ctx) is None or g is None or not seconds:
        return None
    model = model_block(ctx["cfg"])
    least = roofline.min_seconds(
        rl.attention_flops(model,
                           g["index_keys_kept"] - g["decode_keys_full"],
                           g["window_keys"] - g["decode_keys_window"]),
        rl.cache_bytes(model, 0, g["mixed_keys_full"], g["mixed_keys_window"]),
        roofline.peaks_for(ctx["device"]["kind"]))
    return 100.0 * least["seconds"] / seconds
