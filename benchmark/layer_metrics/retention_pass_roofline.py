"""model step: what model_pass_roofline is for the K/V cells, with a state in
place of a KV cache: the least time the chip could take for the traced tail's
model passes (roofline_retention.pass_flops over the bf16 peak, or pass_bytes
over the HBM peak: every weight once a pass, every advancing row's slot read
and written once) over its device-busy time. The tail's work comes from the
program's own counters at the trace's edges (``ragged.passes``,
``decode_tokens``, ``prefill_tokens``, ``step_rows.prefill``); logits are
counted for the decode tokens (a finishing prompt's one row is left out,
which only lowers the share). Source: device_trace. Moves tpot_p50_ms."""

from benchmark.layer_metrics import _common, _retention


def read(ctx):
    from benchmark import roofline, roofline_retention as rr
    from benchmark.sut import model_block

    t = _common.traced(ctx)
    counts = _retention.tail_counts(ctx)
    if t is None or counts is None or not t["busy_s"] or not counts["passes"]:
        return None
    model = model_block(ctx["cfg"])
    least = roofline.min_seconds(
        rr.pass_flops(model, counts["decode_tokens"], counts["prefill_tokens"],
                      counts["chunk_rows"], counts["decode_tokens"]),
        rr.pass_bytes(model, counts["passes"], counts["decode_tokens"],
                      counts["chunk_rows"]),
        roofline.peaks_for(ctx["device"]["kind"]))
    return 100.0 * least["seconds"] / t["busy_s"]
