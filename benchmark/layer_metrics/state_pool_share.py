"""cache: bytes of the state cache's pools (S and z of every layer and slot,
``state_pool.bytes``) over the chip's memory (peaks.json), in percent. What
the configuration file reckons as the state's share of the chip. Source:
program_counter. Moves tpot_p50_ms."""

from benchmark.layer_metrics import _common


def read(ctx):
    from benchmark import roofline

    held = _common.dig(ctx["after"], "state_pool", "bytes")
    if not held or ctx["device"].get("platform") != "tpu":
        return None      # a rehearsal on the CPU has no chip to be a share of
    return 100.0 * held / roofline.peaks_for(ctx["device"]["kind"])["hbm_bytes"]
