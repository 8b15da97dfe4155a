"""cache: prompt tokens served from the prefix cache (``hit_tokens`` gained
over the window) over the prompt tokens of the requests sent in it. Source:
program_counter. Moves ttft_p50_ms."""

from benchmark.layer_metrics import _common


def read(ctx):
    hit = _common.delta(ctx, "prefix", "hit_tokens")
    sent = _common.sent_prompt_tokens(ctx)
    return 100.0 * hit / sent if hit is not None and sent else None
