"""model step: what model_pass_roofline is for the K/V cells, over the latent
page layout: the least time the chip could take for the traced tail's model
passes (roofline_latent.pass_flops over the bf16 peak, or pass_bytes over the
HBM peak: the fixed weights once a pass, a held expert's once where it
received a token, the index keys of every visible token, the latent rows of
the selected or windowed ones) over its device-busy time. The tail's work
comes from the program's own counters at the trace's edges (``latent.*``,
``moe.*``; the passes are ``moe.layer_passes`` over the expert layers, which
counts the mixed passes and the decode chunks' alike); logits are counted for
the ragged steps' decode tokens only, which only lowers the share. It counts
only what the algorithm needs, so it cannot pass 100. Source: device_trace.
Moves tpot_p50_ms."""

from benchmark.layer_metrics import _common, _latent


def read(ctx):
    from benchmark import roofline, roofline_latent as rl
    from benchmark.sut import model_block

    t = _common.traced(ctx)
    g = _latent.gains(ctx, _common.trace_edges(ctx))
    if t is None or g is None or not t["busy_s"] or not g["layer_passes"]:
        return None
    model = model_block(ctx["cfg"])
    n = rl.layer_counts(model)
    passes = g["layer_passes"] / max(n["moe"], 1)
    tokens = g["rows_full"] / max(n["full"], 1)
    least = roofline.min_seconds(
        rl.pass_flops(model, tokens, g["decode_tokens"],
                      g["local_assignments"], g["index_keys_scored"],
                      g["index_keys_kept"], g["window_keys"]),
        rl.pass_bytes(model, passes, g["experts_hit"],
                      g["index_keys_scored"],
                      g["decode_keys_full"] + g["mixed_keys_full"],
                      g["decode_keys_window"] + g["mixed_keys_window"],
                      tokens),
        roofline.peaks_for(ctx["device"]["kind"]))
    return 100.0 * least["seconds"] / t["busy_s"]
