"""model step: the least time the chip could take for the traced tail's
model passes (roofline.py: the larger of operations over the bf16 peak and
bytes over the HBM peak) over its device-busy time. The tail's work is taken
from what the clients saw: output tokens at the window's rate, prompt tokens
at the window's rate less the prefix cache's hit share, contexts at the
records' mean. Every launch is at least one pass over the weights, and a row
advances one token a pass, so passes = the larger of the launches and the
output tokens over the mean number of rows. What is left out (prefill rows'
reads of their earlier context, norms, sampling) only lowers the share.
Source: device_trace. Moves tpot_p50_ms."""

from benchmark.layer_metrics import _common


def read(ctx):
    from benchmark import roofline
    from benchmark.layer_metrics import prefix_hit_share
    from benchmark.sut import model_block

    t, edges = _common.traced(ctx), _common.trace_edges(ctx)
    if t is None or edges is None or not t["busy_s"]:
        return None
    launches = _common.launches(ctx, edges)
    s, win = ctx["summary"], ctx["window"]
    rows = [x["active_slots"] for x in ctx["samples"] if x.get("active_slots")]
    if not launches or not rows or not s.get("out_tok_s"):
        return None
    share = (win["trace_t1"] - win["trace_t0"]) / ctx["window_s"]
    dec_tokens = s["out_tok_s"] * ctx["window_s"] * share
    hit = (prefix_hit_share.read(ctx) or 0.0) / 100.0
    prefill_tokens = _common.sent_prompt_tokens(ctx) * (1.0 - hit) * share
    passes = max(float(launches), dec_tokens / (sum(rows) / len(rows)))
    prompt = s.get("prompt_tokens_mean") or 0.0
    ctx_len = prompt + (s.get("answer_tokens_mean") or 0.0) / 2.0
    model = model_block(ctx["cfg"])
    flops = roofline.pass_flops(
        model, dec_tokens + prefill_tokens, dec_tokens,
        dec_tokens * ctx_len + prefill_tokens * prompt / 2.0)
    nbytes = (passes * roofline.weight_bytes(model)
              + roofline.kv_bytes_per_token(model)
              * (dec_tokens * ctx_len + dec_tokens + prefill_tokens))
    least = roofline.min_seconds(flops, nbytes, roofline.peaks_for(ctx["device"]["kind"]))
    return 100.0 * least["seconds"] / t["busy_s"]
