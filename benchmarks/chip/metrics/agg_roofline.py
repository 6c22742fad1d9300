"""The streaming aggregation's share of its HBM roofline: the least time for
the bytes the aggregation needs (every upload's wire bytes read once, plus
one fp32 global read and one written per round) at the chip's HBM peak, over
the device time of the reducer programs (``_float_reduce``,
``_quant_reduce``, ``_topk_reduce``) in the traced window."""

REDUCERS = ("_float_reduce", "_quant_reduce", "_topk_reduce")


def read(ctx):
    ns, n = ctx.module_time(REDUCERS)
    if not n or ns <= 0:
        return None
    need = ctx.uplink_bytes + 2.0 * ctx.global_bytes * ctx.rounds
    return 100.0 * (need / ctx.peaks["hbm_bytes_per_s"]) / (ns * 1e-9)
