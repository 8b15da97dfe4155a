"""device: peak bytes in use on the fullest chip over the chip's memory
(peaks.json). Source: program_counter. Moves tpot_p50_ms."""


def read(ctx):
    from benchmark import roofline

    peak = ctx["device"].get("memory_peak_bytes")
    if not peak:
        return None
    return 100.0 * peak / roofline.peaks_for(ctx["device"]["kind"])["hbm_bytes"]
