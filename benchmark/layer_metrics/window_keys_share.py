"""cache: keys the sliding layers' kernels had to read over what they would
read without the window bound, over the window (``window.decode_keys_window``
+ ``mixed_keys_window`` over ``window_keys_unbounded``): how much the bound
is doing in this traffic, and the share of the sliding layers' pages a
per-kind page table could free (100 while every context is under the
window). None where the program has no such counter. Source:
program_counter. Moves tpot_p50_ms."""

from benchmark.layer_metrics import _common


def read(ctx):
    decode = _common.delta(ctx, "window", "decode_keys_window")
    mixed = _common.delta(ctx, "window", "mixed_keys_window")
    unbounded = _common.delta(ctx, "window", "window_keys_unbounded")
    if decode is None or mixed is None or not unbounded:
        return None
    return 100.0 * (decode + mixed) / unbounded
