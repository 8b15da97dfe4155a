"""kernels: the paged decode kernel's share of its roofline in the traced
tail, on a model whose layers window: the bytes the algorithm must read (the
K and V rows of every key a row of a chained decode pass sees, by layer kind,
layers counted: ``window.decode_keys_full`` + ``decode_keys_window`` gained
between the trace's edges x roofline_window.key_bytes) over the HBM peak,
against the traced seconds of the operations named paged_attention_decode.
None where the program has no such counter, or the trace lacks the kernel
although rows were chained; 0 where no row was chained in the tail. Source:
device_trace. Moves tpot_p50_ms."""

from benchmark.layer_metrics import _common, _retention, _window

KERNEL = "paged_attention_decode"


def read(ctx):
    from benchmark import roofline, roofline_window as rw
    from benchmark.sut import model_block

    g = _window.gains(ctx, _common.trace_edges(ctx))
    if _common.traced(ctx) is None or g is None:
        return None
    keys = g["decode_keys_full"] + g["decode_keys_window"]
    seconds = _retention.kernel_seconds(ctx, KERNEL)
    if keys and not seconds:
        return None
    if not keys:
        return 0.0
    nbytes = keys * rw.key_bytes(model_block(ctx["cfg"]))
    peaks = roofline.peaks_for(ctx["device"]["kind"])
    return 100.0 * nbytes / peaks["hbm_bytes_per_s"] / seconds
