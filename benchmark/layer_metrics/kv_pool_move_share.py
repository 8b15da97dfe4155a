"""model step: device time of the operations that move a whole KV pool around
the kernels, over device-busy time, in the traced tail: every operation
outside the kernels whose result is one layer's pool ``[Hkv, N, P, D]`` or
the stack of them (its short name holds ``_{Hkv}_{N}_{P}_``; names are cut at
64 characters, so the match is on the middle of the shape, not its end). A
step that carries the stacked pools through its layer scan and names the layer
inside the write and the kernels has none left: the target is zero. Hkv from
the cell's configuration, N and P from the program's ``kv_pool`` block.
Source: device_trace. Moves tpot_p50_ms."""

from benchmark import xplane
from benchmark.layer_metrics import _common


def read(ctx):
    t = _common.traced(ctx)
    hkv = _common.dig(ctx.get("cfg"), "num_key_value_heads")
    pages = _common.dig(ctx.get("after"), "kv_pool", "num_pages")
    page = _common.dig(ctx.get("after"), "kv_pool", "page_size")
    if not t or not t.get("busy_s") or None in (hkv, pages, page):
        return None
    pool = "_{}_{}_{}_".format(hkv, pages, page)
    moved = sum(seconds for name, seconds, _ in t.get("ops") or []
                if pool in name and not xplane.is_kernel(name))
    return 100.0 * moved / t["busy_s"]
