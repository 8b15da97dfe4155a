"""What the readers of a state-space mixer share: the traced tail's counts
from the program's ``ssm`` and ``ragged`` blocks, and the two SSD kernels'
traced seconds by name. Each returns None where the program has no such
counter (a program without a row state beside its pages: the parent) or the
trace no such kernel."""

from benchmark.layer_metrics import _common, _retention

UPDATE = "mamba2_ssd_update"
CHUNK = "mamba2_ssd_chunk"
kernel_seconds = _retention.kernel_seconds


def tail_counts(ctx):
    """Over the traced tail: rows x passes through the update kernel, the
    chunk kernel's rows and tokens, the model's passes, and what the paged
    kernels were given beside them."""
    edges = _common.trace_edges(ctx)
    if edges is None or _common.dig(edges[1], "ssm") is None:
        return None
    out = {name: _common.delta(ctx, "ssm", name, edges=edges)
           for name in ("update_rows", "chunk_rows", "chunk_tokens", "passes")}
    out.update({name: _common.delta(ctx, "ragged", name, edges=edges)
                for name in ("decode_chain_kv_tokens", "mixed_kv_tokens",
                             "mixed_qk_pairs")})
    return None if any(v is None for v in out.values()) else out
