"""model step: what model_pass_roofline is for the K/V cells, on a model
whose layers window and route: the least time the chip could take for the
traced tail's model passes (roofline_window.pass_flops over the bf16 peak,
or pass_bytes over the HBM peak: every non-expert weight once a pass, a
routed expert once where it received a token, the visible keys by layer
kind, operations at top-k + shared) over its device-busy time. The tail's
work comes from the program's own counters at the trace's edges
(``window.*``, ``moe.*``; the passes are ``moe.layer_passes`` over the
expert layers, which counts the mixed passes and the decode chunks' alike);
logits are counted for the ragged steps' decode tokens only, which only
lowers the share. It counts only what the algorithm needs, so it cannot
pass 100. Source: device_trace. Moves tpot_p50_ms."""

from benchmark.layer_metrics import _common, _window


def read(ctx):
    from benchmark import roofline, roofline_window as rw
    from benchmark.sut import model_block

    t = _common.traced(ctx)
    g = _window.gains(ctx, _common.trace_edges(ctx))
    if t is None or g is None or not t["busy_s"] or not g["layer_passes"]:
        return None
    model = model_block(ctx["cfg"])
    n = rw.layer_counts(model)
    passes = g["layer_passes"] / max(n["moe"], 1)
    tokens = (g["rows_full"] + g["rows_window"]) / max(
        n["full"] + n["window"], 1)
    decode_keys = g["decode_keys_full"] + g["decode_keys_window"]
    least = roofline.min_seconds(
        rw.pass_flops(model, tokens, g["decode_tokens"],
                      g["local_assignments"],
                      decode_keys + g["mixed_pairs_full"]
                      + g["mixed_pairs_window"]),
        rw.pass_bytes(model, passes, g["experts_hit"],
                      decode_keys + g["mixed_keys_full"]
                      + g["mixed_keys_window"], tokens),
        roofline.peaks_for(ctx["device"]["kind"]))
    return 100.0 * least["seconds"] / t["busy_s"]
