"""model step: what model_pass_roofline is for the K/V cells, on a model
whose every block runs a state-space mixer beside attention: the least time
the chip could take for the traced tail's model passes
(roofline_ssd.pass_flops over the bf16 peak, or pass_bytes over the HBM
peak: every weight once a pass, the state of every row that advanced read
and written once a layer, K and V of every token a row attended) over its
device-busy time. The tail's work comes from the program's own counters at
the trace's edges (``ssm.passes`` / ``update_rows`` / ``chunk_rows`` /
``chunk_tokens``, ``ragged.decode_chain_kv_tokens`` / ``mixed_kv_tokens`` /
``mixed_qk_pairs``); logits are counted for the one-token rows only (a
finishing prompt's row is left out, which only lowers the share). It counts
only what the algorithm needs, so it cannot pass 100. Source: device_trace.
Moves tpot_p50_ms."""

from benchmark.layer_metrics import _common, _ssd


def read(ctx):
    from benchmark import roofline, roofline_ssd as rs
    from benchmark.sut import model_block

    t = _common.traced(ctx)
    c = _ssd.tail_counts(ctx)
    if t is None or c is None or not t["busy_s"] or not c["passes"]:
        return None
    model = model_block(ctx["cfg"])
    least = roofline.min_seconds(
        rs.pass_flops(model, c["update_rows"], c["chunk_tokens"],
                      c["update_rows"],
                      c["decode_chain_kv_tokens"] + c["mixed_qk_pairs"]),
        rs.pass_bytes(model, c["passes"], c["update_rows"], c["chunk_rows"],
                      c["chunk_tokens"],
                      c["decode_chain_kv_tokens"] + c["mixed_kv_tokens"]),
        roofline.peaks_for(ctx["device"]["kind"]))
    return 100.0 * least["seconds"] / t["busy_s"]
